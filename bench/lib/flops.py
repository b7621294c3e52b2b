"""Work that a Mamba language model's training requires per token,
computed from its shapes and nothing else: no schedule, kernel or
recomputation of the program changes it.

Forward, per token:
  matmuls      2 x (every projection of every layer + the head); the
               embedding is a lookup and costs nothing
  conv         2 x d_conv x d_inner per layer
  scan         per layer and per (channel, state) pair 7 operations:
               delta*A, exp, (delta*u)*B, s*a, + b, C*s, the sum over
               states; per channel 3 more: delta*u, D*u and its add
Training is three times the forward pass (the backward pass costs two).
"""
from __future__ import annotations

from bench.lib.model import Sizes


def matmul_params(s: Sizes) -> int:
    per_layer = (s.d * 2 * s.di + s.di * (s.R + 2 * s.N) + s.R * s.di +
                 s.di * s.d)
    return s.layers * per_layer + s.d * s.vocab


def forward_flops_per_token(s: Sizes) -> int:
    scan = 7 * s.di * s.N + 3 * s.di
    conv = 2 * s.K * s.di
    return 2 * matmul_params(s) + s.layers * (scan + conv)


def train_flops_per_token(s: Sizes) -> int:
    return 3 * forward_flops_per_token(s)
