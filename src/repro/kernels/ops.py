"""jit'd public wrappers for the Pallas kernels: padding to tile multiples,
layout transposes, custom_vjp wiring, and backend dispatch.

``backend='xla'`` routes to the chunked pure-JAX implementations in
repro.core (SPMD-partitionable and visible to cost_analysis);
``backend='pallas'`` routes to the TPU kernels — compiled by Mosaic on a
TPU, run under the Pallas interpreter on the CPU (kernels/__init__.py
decides from the platform the call is lowered for). Which backend a model
block's scan takes is decided above this module, by
models/blocks.py::_scan_backend. A Pallas call is not SPMD-partitioned, so
under an abstract mesh of more than one device the scan kernel runs per
shard inside ``shard_map``.

Structure note: the custom_vjp is defined over *already padded, fully
normalized* operands (no Nones, tile-multiple shapes); the public wrappers
pad/transpose outside it, so cotangent padding/slicing falls out of autodiff
instead of hand-written bookkeeping.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import conv as core_conv
from repro.core import ssm as core_ssm
from repro.distributed import sharding as shd
from repro.kernels import conv1d_pack as conv_k
from repro.kernels import selective_scan as scan_k

_F0 = jax.dtypes.float0


def _chunk(chunk: int, L: int) -> int:
    """Kernel chunk length: ``chunk``, or L rounded up to whole 8-row
    sublane groups when the sequence is shorter than one chunk."""
    return min(chunk, -(-L // 8) * 8)


def _positions(positions, Bz: int, L: int, T: int):
    """(B, L, 1) i32 position column padded to whole chunks with pos=1 (no
    reset); ``None`` means one unpacked sequence per row."""
    pos = positions if positions is not None else \
        jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (Bz, L))
    return _pad_to(pos.astype(jnp.int32), 1, T, value=1)[..., None]


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan_padded(u, delta, At, B, C, Dp, pos, block_d, chunk):
    y, _ = _scan_fwd_rule(u, delta, At, B, C, Dp, pos, block_d, chunk)
    return y


def _scan_fwd_rule(u, delta, At, B, C, Dp, pos, block_d, chunk):
    y, ckpts = scan_k.selective_scan_fwd_pallas(
        u, delta, At, B, C, Dp, pos, block_d=block_d, chunk=chunk)
    return y, (u, delta, At, B, C, Dp, pos, ckpts)


def _scan_bwd_rule(block_d, chunk, res, dy):
    u, delta, At, B, C, Dp, pos, ckpts = res
    du, ddelta, dB_p, dC_p, dA_p, dD_p = scan_k.selective_scan_bwd_pallas(
        u, delta, At, B, C, Dp, pos, ckpts, dy, block_d=block_d, chunk=chunk)
    return (du.astype(u.dtype), ddelta.astype(delta.dtype),
            dA_p.sum(0).astype(At.dtype),
            dB_p.sum(1).reshape(B.shape).astype(B.dtype),
            dC_p.sum(1).reshape(C.shape).astype(C.dtype),
            dD_p.sum(0).astype(Dp.dtype),
            np.zeros(pos.shape, _F0))


_scan_padded.defvjp(_scan_fwd_rule, _scan_bwd_rule)


def selective_scan(u, delta, A, B, C, D=None, positions=None, *,
                   backend: str = "xla", block_d: int = scan_k.DEF_BLOCK_D,
                   chunk: int = scan_k.DEF_CHUNK_T, xla_chunk: int = 256,
                   xla_method: str = "blocked", xla_dtype=None,
                   xla_intra=None):
    """Fused segmented selective scan. See kernels/ref.py for semantics.

    u, delta: (B, L, Dm) | A: (Dm, N) | B, C: (B, L, N) | D: (Dm,) |
    positions: (B, L) i32 (reset where == 0) → y (B, L, Dm).

    ``backend``: 'xla' — core/ssm.py's evaluators (``xla_method``,
    ``xla_chunk``, ``xla_dtype``, ``xla_intra``) | 'pallas' — the
    per-step walk kernel (kernels/selective_scan.py; ``block_d`` channels
    by ``chunk`` rows a grid step) and its backward, through one
    custom_vjp.
    """
    if backend == "xla":
        return core_ssm.selective_scan(u, delta, A, B, C, D,
                                       positions=positions,
                                       method=xla_method, chunk=xla_chunk,
                                       compute_dtype=xla_dtype,
                                       intra=xla_intra)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    return _scan_pallas_on_mesh(u, delta, A, B, C, D, positions,
                                block_d=block_d, chunk=chunk)


def _scan_pallas_on_mesh(u, delta, A, B, C, D, positions, **kw):
    """The scan kernel on the abstract mesh the call is traced under. With
    more than one device it runs per shard — rows over the data axes,
    channels over "model", each where it divides: both are independent in
    the scan, and B, C are shared by every channel (their cotangents are
    summed over the model axis by ``shard_map``'s transpose)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return _scan_pallas(u, delta, A, B, C, D, positions, **kw)
    Bz, L, Dm = u.shape
    rows, ch = shd.batch_axis(mesh, Bz), shd.model_axis(mesh, Dm)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (Bz, L))
    if D is None:
        D = jnp.zeros(Dm, u.dtype)
    x, bc = P(rows, None, ch), P(rows, None, None)
    return jax.shard_map(
        functools.partial(_scan_pallas, **kw), mesh=mesh,
        in_specs=(x, x, P(ch, None), bc, bc, P(ch), P(rows, None)),
        out_specs=x, check_vma=False)(u, delta, A, B, C, D, positions)


def _scan_pallas(u, delta, A, B, C, D, positions, *, block_d, chunk):
    """The scan kernel on one device's operands: pad to whole tiles, run
    the custom_vjp, slice the padding off."""
    Bz, L, Dm = u.shape
    bd = min(block_d, max(Dm, 8))
    T = _chunk(chunk, L)
    # channel padding: A=0 ⇒ a=1 but b=0 keeps padded h = 0; y sliced off
    up, dtp = _pad_to(u, 2, bd), _pad_to(delta, 2, bd)
    At = _pad_to(A.T, 1, bd)
    Dp = _pad_to((D if D is not None else jnp.zeros(Dm, u.dtype))[None, :],
                 1, bd)
    # L padding: pos=1 (no reset), delta=0 ⇒ a=1 carry; y sliced off
    up, dtp = _pad_to(up, 1, T), _pad_to(dtp, 1, T)
    Bp, Cp = _pad_to(B, 1, T), _pad_to(C, 1, T)
    posp = _positions(positions, Bz, L, T)
    y = _scan_padded(up, dtp, At, Bp, Cp, Dp, posp, bd, T)
    return y[:, :L, :Dm]


# ---------------------------------------------------------------------------
# head-structured selective scan (Mamba-2 / SSD, scalar per-head decay)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _scan_heads_padded(u, delta, Ah, B, C, Dp, pos, chunk, schedule, sub_t):
    y, _ = _scan_heads_fwd_rule(u, delta, Ah, B, C, Dp, pos, chunk,
                                schedule, sub_t)
    return y


def _scan_heads_fwd_rule(u, delta, Ah, B, C, Dp, pos, chunk, schedule,
                         sub_t):
    y, ckpts = scan_k.selective_scan_heads_fwd_pallas(
        u, delta, Ah, B, C, Dp, pos, chunk=chunk, schedule=schedule,
        sub_t=sub_t)
    return y, (u, delta, Ah, B, C, Dp, pos, ckpts)


def _scan_heads_bwd_rule(chunk, schedule, sub_t, res, dy):
    # one backward serves both forward schedules: the ckpt contract is
    # identical and the adjoint math is schedule-independent
    u, delta, Ah, B, C, Dp, pos, ckpts = res
    du, ddelta, dB_p, dC_p, dA_p, dD_p = \
        scan_k.selective_scan_heads_bwd_pallas(
            u, delta, Ah, B, C, Dp, pos, ckpts, dy, chunk=chunk,
            sub_t=sub_t)
    return (du.astype(u.dtype), ddelta.astype(delta.dtype),
            dA_p.sum(0).astype(Ah.dtype), dB_p.sum(1).astype(B.dtype),
            dC_p.sum(1).astype(C.dtype), dD_p.sum(0).astype(Dp.dtype),
            np.zeros(pos.shape, _F0))


_scan_heads_padded.defvjp(_scan_heads_fwd_rule, _scan_heads_bwd_rule)


def selective_scan_heads(u, delta, A, B, C, D=None, positions=None, *,
                         backend: str = "xla",
                         chunk: int = scan_k.DEF_CHUNK_T,
                         xla_chunk: int = 64, xla_method: str = "blocked",
                         xla_dtype=None, xla_intra=None,
                         schedule: str = "blocked_heads", sub_t=None):
    """Fused head-structured segmented selective scan (scalar per-head
    decay — Mamba-2/SSD). See core/ssm.py::selective_scan_heads for
    semantics; this wrapper adds backend dispatch.

    u: (B, L, H, dh) | delta: (B, L, H) | A: (H,) | B, C: (B, L, N) |
    D: (H,) | positions: (B, L) i32 (reset where == 0) → y (B, L, H, dh).

    ``backend='xla'`` routes to the core evaluators (``xla_intra``:
    'quad' | 'dual' in-chunk form); ``backend='pallas'`` transposes to the
    head-major kernel layout ((B, H, L, dh)), pads L to the chunk, and runs
    the ``schedule`` kernels ('blocked_heads' | 'blocked_heads_dual', with
    optional subtile ``sub_t``) through a custom_vjp (the shared
    transpose-contraction backward).
    """
    if backend == "xla":
        return core_ssm.selective_scan_heads(u, delta, A, B, C, D,
                                             positions=positions,
                                             method=xla_method,
                                             chunk=xla_chunk,
                                             compute_dtype=xla_dtype,
                                             intra=xla_intra)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    if schedule not in ("blocked_heads", "blocked_heads_dual"):
        raise ValueError(f"unknown heads schedule {schedule!r}")
    Bz, L, H, P = u.shape
    T = _chunk(chunk, L)
    uh = jnp.moveaxis(u, 2, 1)                       # (B, H, L, P)
    dth = jnp.moveaxis(delta, 2, 1)[..., None]       # (B, H, L, 1)
    Ah = A.astype(jnp.float32)[:, None, None]        # (H, 1, 1)
    Dp = (D if D is not None else
          jnp.zeros(H, u.dtype)).astype(jnp.float32)[:, None, None]
    # L padding: pos=1 (no reset), delta=0 ⇒ decay 1 / b-term 0 (carry)
    uh, dth = _pad_to(uh, 2, T), _pad_to(dth, 2, T)
    Bp, Cp = _pad_to(B, 1, T), _pad_to(C, 1, T)
    posp = _positions(positions, Bz, L, T)
    y = _scan_heads_padded(uh, dth, Ah, Bp, Cp, Dp, posp, T, schedule, sub_t)
    return jnp.moveaxis(y, 1, 2)[:, :L]


# ---------------------------------------------------------------------------
# conv1d pack
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _conv_padded(x, weight, bias, pos, block_d, chunk):
    y, _ = _conv_fwd_rule(x, weight, bias, pos, block_d, chunk)
    return y


def _conv_fwd_rule(x, weight, bias, pos, block_d, chunk):
    y = conv_k.conv1d_pack_fwd_pallas(x, weight, bias, pos,
                                      block_d=block_d, chunk=chunk)
    return y, (x, weight, bias, pos)


def _conv_bwd_rule(block_d, chunk, res, dy):
    x, weight, bias, pos = res
    W = weight.shape[0]
    dx = conv_k.conv1d_pack_bwd_dx_pallas(dy, weight, pos,
                                          block_d=block_d, chunk=chunk)
    # dweight / dbias: tiny O(W·D) reductions — XLA einsum (see kernel doc)
    Lp = x.shape[1]
    dy32, x32 = dy.astype(jnp.float32), x.astype(jnp.float32)
    dws = []
    for k in range(W):                    # weight row j = W-1-k ↔ back-off k
        shifted = jnp.pad(x32, ((0, 0), (k, 0), (0, 0)))[:, :Lp]
        masked = jnp.where(pos >= k, shifted, 0.0)
        dws.append(jnp.einsum("bld,bld->d", dy32, masked))
    dw = jnp.stack(dws[::-1], axis=0).astype(weight.dtype)
    dbias = dy32.sum((0, 1))[None, :].astype(bias.dtype)
    return (dx.astype(x.dtype), dw, dbias, np.zeros(pos.shape, _F0))


_conv_padded.defvjp(_conv_fwd_rule, _conv_bwd_rule)


def conv1d_pack(x, weight, bias=None, positions=None, *,
                backend: str = "xla", block_d: int = conv_k.DEF_BLOCK_D,
                chunk: int = conv_k.DEF_CHUNK_T):
    """Segmented causal depthwise conv. x (B,L,D) | weight (W,D) | bias (D,)."""
    if backend == "xla":
        return core_conv.conv1d_pack(x, weight, bias, positions)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    Bz, L, Dm = x.shape
    bd = min(block_d, max(Dm, 8))
    T = _chunk(chunk, L)
    xp = _pad_to(_pad_to(x, 2, bd), 1, T)
    wp = _pad_to(weight, 1, bd)
    bp = _pad_to((bias if bias is not None else
                  jnp.zeros(Dm, x.dtype))[None, :], 1, bd)
    posp = _positions(positions, Bz, L, T)
    y = _conv_padded(xp, wp, bp, posp, bd, T)
    return y[:, :L, :Dm]
