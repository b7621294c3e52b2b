"""Pallas TPU kernel for conv1d_pack (paper Algorithm 1, fwd + dx bwd).

Causal depthwise conv, width W (Mamba uses 4), with PackMamba boundary
truncation: the tap reaching back k positions is dropped when
k > position_indices[t].

Layout: time on sublanes, channels on lanes. Positions arrive as a
(B, L, 1) column so that a (T, 1) block broadcasts across the channel lanes
with no relayout.

Halo handling: Pallas BlockSpecs don't express halos, so the kernel receives
the *previous* L-chunk as a second view of x (index map ``l-1`` clamped at 0).
Tap k is a sublane rotation by k of the current block whose first k rows
come from the same rotation of the previous block — no unaligned slice.
Tokens that would reach before the packed buffer are always masked by the
position test (positions[t] ≤ t for any packed layout — a sequence's start
can never precede buffer start), so the clamped duplicate block at l = 0 is
never actually read through.

The dx backward needs the *next* chunk of dy (reverse-index halo — the
paper's "reverse indices" of §3.3/§3.5); at the last chunk the halo is
explicitly zeroed. dweight/dbias are cheap O(W·D) reductions left to XLA in
ops.py (documented split: the sequence-structured, bandwidth-bound work is
in the kernel; the tiny parameter reductions are not).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import pallas_call

DEF_BLOCK_D = 128
DEF_CHUNK_T = 256
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_kernel(pos_ref, xc_ref, xp_ref, w_ref, b_ref, y_ref):
    """pos (1,T,1) | x cur/prev (1,T,bd) | w (W,bd) | b (1,bd) | y (1,T,bd).
    y[t] = b + Σ_k w[W-1-k]·x[t-k]·(pos[t] ≥ k)."""
    W = w_ref.shape[0]
    x_cur = xc_ref[0].astype(jnp.float32)          # (T, bd)
    x_prev = jnp.where(pl.program_id(2) == 0, 0.0,
                       xp_ref[0].astype(jnp.float32))
    pos = pos_ref[0]                               # (T, 1) i32
    row = jax.lax.broadcasted_iota(jnp.int32, x_cur.shape, 0)
    acc = b_ref[...].astype(jnp.float32) + \
        w_ref[W - 1:W, :].astype(jnp.float32) * x_cur
    for k in range(1, W):                          # static unroll
        seg = jnp.where(row < k, pltpu.roll(x_prev, k, 0),
                        pltpu.roll(x_cur, k, 0))   # x[t-k]
        seg = jnp.where(pos >= k, seg, 0.0)
        acc = acc + w_ref[W - 1 - k:W - k, :].astype(jnp.float32) * seg
    y_ref[0] = acc.astype(y_ref.dtype)


def conv1d_pack_fwd_pallas(x, weight, bias, positions,
                           block_d: int = DEF_BLOCK_D,
                           chunk: int = DEF_CHUNK_T):
    """x (B, L, Dm) | weight (W, Dm) | bias (1, Dm) | positions (B, L, 1)
    i32. All pre-padded to multiples of (chunk, block_d). Returns y
    (B, L, Dm)."""
    Bz, L, Dm = x.shape
    T, bd = chunk, block_d
    grid = (Bz, Dm // bd, L // T)
    W = weight.shape[0]
    prev = lambda l: jnp.maximum(l - 1, 0)
    return pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T, 1), lambda b, d, l: (b, l, 0)),
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, l, d)),
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, prev(l), d)),
            pl.BlockSpec((W, bd), lambda b, d, l: (0, d)),
            pl.BlockSpec((1, bd), lambda b, d, l: (0, d)),
        ],
        out_specs=pl.BlockSpec((1, T, bd), lambda b, d, l: (b, l, d)),
        out_shape=jax.ShapeDtypeStruct((Bz, L, Dm), x.dtype),
        compiler_params=_PARAMS,
    )(positions, x, x, weight, bias)


def _bwd_dx_kernel(posc_ref, posn_ref, dyc_ref, dyn_ref, w_ref, dx_ref):
    """dx[t] = Σ_k w[W-1-k]·dy[t+k]·(pos[t+k] ≥ k) — reverse-index halo:
    tap k rotates the masked current block up by k, its last k rows taken
    from the next block (zero past the last one)."""
    T = dyc_ref.shape[1]
    W = w_ref.shape[0]
    is_last = pl.program_id(2) == pl.num_programs(2) - 1
    dy_cur = dyc_ref[0].astype(jnp.float32)
    dy_nxt = jnp.where(is_last, 0.0, dyn_ref[0].astype(jnp.float32))
    row = jax.lax.broadcasted_iota(jnp.int32, dy_cur.shape, 0)
    acc = w_ref[W - 1:W, :].astype(jnp.float32) * dy_cur
    for k in range(1, W):
        z_cur = jnp.where(posc_ref[0] >= k, dy_cur, 0.0)
        z_nxt = jnp.where(posn_ref[0] >= k, dy_nxt, 0.0)
        seg = jnp.where(row >= T - k, pltpu.roll(z_nxt, T - k, 0),
                        pltpu.roll(z_cur, T - k, 0))   # z[t+k]
        acc = acc + w_ref[W - 1 - k:W - k, :].astype(jnp.float32) * seg
    dx_ref[0] = acc.astype(dx_ref.dtype)


def conv1d_pack_bwd_dx_pallas(dy, weight, positions,
                              block_d: int = DEF_BLOCK_D,
                              chunk: int = DEF_CHUNK_T):
    Bz, L, Dm = dy.shape
    T, bd = chunk, block_d
    grid = (Bz, Dm // bd, L // T)
    W = weight.shape[0]
    nxt = lambda l: jnp.minimum(l + 1, (L // T) - 1)
    return pallas_call(
        _bwd_dx_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T, 1), lambda b, d, l: (b, l, 0)),
            pl.BlockSpec((1, T, 1), lambda b, d, l: (b, nxt(l), 0)),
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, l, d)),
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, nxt(l), d)),
            pl.BlockSpec((W, bd), lambda b, d, l: (0, d)),
        ],
        out_specs=pl.BlockSpec((1, T, bd), lambda b, d, l: (b, l, d)),
        out_shape=jax.ShapeDtypeStruct((Bz, L, Dm), jnp.float32),
        compiler_params=_PARAMS,
    )(positions, positions, dy, dy, weight)
