"""Pure-jnp oracles for the Pallas kernels.

Deliberately written as the most naive sequential formulation (python-level
math, lax.scan over single timesteps, no chunking) so they are independent of
both the Pallas kernels and the optimized XLA path in core/ — every test
triangulates kernel ↔ oracle ↔ core path.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def selective_scan_ref(u: jnp.ndarray, delta: jnp.ndarray, A: jnp.ndarray,
                       B: jnp.ndarray, C: jnp.ndarray,
                       D: Optional[jnp.ndarray] = None,
                       positions: Optional[jnp.ndarray] = None,
                       ) -> jnp.ndarray:
    """u, delta: (Bz, L, Dm) | A: (Dm, N) | B, C: (Bz, L, N) | D: (Dm,).

    h_t = exp(Δ_t A)·h_{t-1} + (Δ_t B_t)·u_t ;  y_t = C_t·h_t + D·u_t
    with Ā→0 where positions == 0 (PackMamba reset). All math f32.
    """
    Bz, L, Dm = u.shape
    N = A.shape[-1]
    f = jnp.float32
    u32, d32 = u.astype(f), delta.astype(f)
    A32, B32, C32 = A.astype(f), B.astype(f), C.astype(f)
    reset = (positions == 0) if positions is not None else \
        jnp.zeros((Bz, L), bool)

    def step(h, xs):
        u_t, d_t, B_t, C_t, r_t = xs
        a_t = jnp.exp(d_t[..., None] * A32)              # (Bz, Dm, N)
        a_t = jnp.where(r_t[:, None, None], 0.0, a_t)
        b_t = (d_t * u_t)[..., None] * B_t[:, None, :]   # (Bz, Dm, N)
        h = a_t * h + b_t
        y_t = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y_t

    h0 = jnp.zeros((Bz, Dm, N), f)
    xs = (jnp.moveaxis(u32, 1, 0), jnp.moveaxis(d32, 1, 0),
          jnp.moveaxis(B32, 1, 0), jnp.moveaxis(C32, 1, 0),
          jnp.moveaxis(reset, 1, 0))
    _, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1)
    if D is not None:
        y = y + D.astype(f) * u32
    return y.astype(u.dtype)


def conv1d_pack_ref(x: jnp.ndarray, weight: jnp.ndarray,
                    bias: Optional[jnp.ndarray] = None,
                    positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """x: (Bz, L, Dm) | weight: (W, Dm) | bias: (Dm,) | positions: (Bz, L).

    Causal depthwise conv; tap reaching back k is dropped when
    k > positions[t] (Algorithm 1)."""
    W = weight.shape[0]
    f = jnp.float32
    x32 = x.astype(f)
    y = jnp.zeros(x.shape, f)
    for k in range(W):
        # tap k: x[t - k] (zero before the buffer start)
        tap = jnp.pad(x32, ((0, 0), (k, 0), (0, 0)))[:, :x.shape[1]]
        tap = tap * weight[W - 1 - k].astype(f)
        if positions is not None:
            tap = jnp.where((positions >= k)[..., None], tap, 0.0)
        y = y + tap
    if bias is not None:
        y = y + bias.astype(f)
    return y.astype(x.dtype)
