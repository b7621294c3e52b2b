"""Pallas TPU kernel for the PackMamba segmented selective scan (fwd + bwd).

TPU adaptation of the paper's modified `ScanOp_pack` (Algorithm 2) + §3.5
co-optimization. The CUDA version modifies a Blelloch tree scan
(scanMul/scanAdd) and stages position_indices HBM→SRAM→registers with
coalesced loads. The TPU-native reformulation:

  * Grid ``(B, D/bd, L/T)`` with semantics ("parallel", "parallel",
    "arbitrary"): batch and channel blocks are embarrassingly parallel; the
    sequence-chunk dimension is sequential and the recurrent state ``h``
    lives in a VMEM scratch that persists across grid steps along it —
    the TPU analogue of the chunk-carried scan.
  * Every in-kernel value is 2-D with time on sublanes and channels on
    lanes; the ``(N=16, bd=128)`` state tile is one f32 VREG pair, so the
    per-step update ``h = a⊙h + b`` is pure VPU work with no relayout.
    Row reads are aligned groups (``pl.multiple_of``) and per-step scalars
    come from static slices of those groups.
  * ``position_indices`` ride the same BlockSpec pipeline as the
    activations: one (T, 1) int32 column per grid step (positions arrive
    as (B, L, 1)) — a single DMA amortized over the whole (T × bd) tile,
    the VMEM counterpart of the paper's coalesced-HBM/SRAM staging. The
    reset test ``pos[t] == 0`` folds into the decay (Ā→0), costing zero
    extra memory passes — their "no extra kernel overhead" property.
  * Cumulative sums (decay exponents, reset counts) are matmuls with a
    lower-triangular ones matrix on the MXU, at f32 (``HIGHEST``)
    precision.
  * The backward pass (paper §3.4: "modifications only require setting
    Ā_{pos==0}→0" in the reverse scans) is a second kernel that walks the
    L-grid in *reverse*, recomputes h within each chunk from a per-chunk
    checkpoint saved by the forward (flash-style recompute: checkpoints are
    L/T× smaller than the full state trajectory), and carries the adjoint
    in VMEM scratch.

VMEM per grid step at the defaults (T=256, bd=128, N=16): blocks ≈ 1.5 MiB
double-buffered. The Mamba-1 backward adds the recomputed states hbuf
((T+1)·N, bd), 2 MiB, and one row group's stacked (G·N, bd) tiles of
adjoints, states and products, 256 KiB each at G 32, which Mosaic keeps
in its own scratch. It fits v5e's 16 MiB scoped default; it does not
compile at bd=256.

Schedules:
  * Mamba-1 (per-channel decay, ``selective_scan_{fwd,bwd}_pallas``) —
                  per-step walk over aligned row groups, one exponential
                  per (t, d, n); matches the paper's ScanOp_pack closely.
                  On a v5e its forward+backward takes 31 ms per layer at
                  (8, 4096, 2048, N 16), forward 11.1 and backward 20.0
                  (52.4 with the per-step backward, when the pair took
                  64 ms against 372 ms for the XLA blocked evaluator).
  * ``blocked_heads`` — head-structured (Mamba-2/SSD proper): grid
                  ``(B, H, L/T)``, per-head SCALAR decay, state (dh, N) per
                  head in VMEM scratch. The masked cumulative-decay matrix
                  is one (Tt, Tt) tile per head; the in-chunk states are
                  formed explicitly, one state column n at a time, each a
                  (Tt, Tt) @ (Tt, dh) matmul.
  * ``blocked_heads_dual`` — the attention-like dual form of
                  ``blocked_heads`` (structured-state-space duality): the
                  (Tt, Tt) decay folds into a C·Bᵀ Gram matrix and outputs
                  come straight from (Tt, Tt) @ (Tt, dh) matmuls without
                  forming the in-chunk states. Both heads schedules share
                  one backward kernel, written in the same dual form
                  (identical ckpt contract; adjoint math is schedule-free).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import pallas_call

DEF_BLOCK_D = 128
DEF_CHUNK_T = 256      # on a TPU v5e the Mamba-1 kernel's forward+backward
#   at (8, 4096, 2048, N 16) read 64.7 / 64.4 / 64.1 / 64.0 ms at chunks
#   128 / 256 / 512 / 1024 with the per-step backward (not measured since)
DEF_SUB_T = 16     # rows per group of the Mamba-1 forward's walk, and the
#   heads schedules' default in-chunk subtile

f32 = jnp.float32
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ bᵀ
_TN = ((0,), (0,))      # aᵀ @ b


def _pick_subtile(T: int, sub_t=None) -> int:
    """Subtile length for a chunk of length T: the explicit request when
    given, else the largest supported default dividing the chunk.

    A requested ``sub_t`` that does not divide T degrades to the largest
    divisor ≤ the request instead of raising, so a subtile chosen for one
    sequence length never turns a call at another into a trace-time
    error."""
    if sub_t:
        st = min(int(sub_t), T)
        while T % st:
            st -= 1
        return st
    for tt in (DEF_SUB_T, 8, 4, 2, 1):
        if T % tt == 0:
            return tt
    return 1


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=f32)


def _tri(n: int, strict: bool = False, upper: bool = False):
    """(n, n) f32 ones where j ≤ i (j < i if strict); transposed if upper.
    ``_tri(n) @ x`` is the inclusive cumulative sum of x along rows."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    if upper:
        i, j = j, i
    return ((j < i) if strict else (j <= i)).astype(f32)


def _last(v):
    """Last row of a (tt, 1) column as a (1, 1) value. Built by a reduction:
    Mosaic cannot broadcast a sliced (1, 1) across both sublanes and lanes."""
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    return jnp.sum(jnp.where(row == v.shape[0] - 1, v, 0.0), axis=0,
                   keepdims=True)


def _rowsel(n: int, width: int):
    """Row index of an (n, width) tile, for assembling rows with where()."""
    return jax.lax.broadcasted_iota(jnp.int32, (n, width), 0)


def _rows(pos_ref, u_ref, dt_ref, Bm_ref, Cm_ref, t0, n):
    """Rows [t0, t0+n) of one chunk as f32: dt, u (n, bd); B, C (n, N);
    keep (n, 1) — False where a packed sequence starts (the Ā→0 reset)."""
    sl = pl.ds(t0, n)
    return (dt_ref[0, sl, :].astype(f32), u_ref[0, sl, :].astype(f32),
            Bm_ref[0, sl, :].astype(f32), Cm_ref[0, sl, :].astype(f32),
            pos_ref[0, sl, :] != 0)


def _at(i, n: int):
    """Start row of the i-th group of n rows, marked aligned for Mosaic."""
    return pl.multiple_of(i * n, n)


# ---------------------------------------------------------------------------
# Mamba-1 (per-channel decay) forward kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(pos_ref, u_ref, dt_ref, At_ref, Bm_ref, Cm_ref, Dp_ref,
                y_ref, ckpt_ref, h_ref):
    """One (b, d-block, l-chunk) grid step, per-step walk.

    pos (1,T,1) i32 | u, dt (1,T,bd) | At (N,bd) | Bm, Cm (1,T,N) |
    Dp (1,bd) | y (1,T,bd) | ckpt (1,1,N,bd) — chunk-entry state |
    h scratch (N,bd) f32.
    """
    T = u_ref.shape[1]
    G = _pick_subtile(T)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    ckpt_ref[0, 0] = h_ref[...]          # h at chunk entry (for backward)
    At = At_ref[...].astype(f32)                  # (N, bd)
    Dp = Dp_ref[...].astype(f32)                  # (1, bd)
    row = _rowsel(G, At.shape[1])

    def group(gi, _):
        t0 = _at(gi, G)
        dt, u, Bv, Cv, keep = _rows(pos_ref, u_ref, dt_ref, Bm_ref, Cm_ref,
                                    t0, G)
        BT, CT = Bv.T, Cv.T                       # (N, G)
        du = dt * u
        kf = jnp.where(keep, jnp.ones_like(dt), jnp.zeros_like(dt))
        h = h_ref[...]
        y = Dp * u
        for i in range(G):
            a = jnp.exp(dt[i:i + 1] * At) * kf[i:i + 1]
            h = a * h + BT[:, i:i + 1] * du[i:i + 1]
            y_i = jnp.sum(h * CT[:, i:i + 1], axis=0, keepdims=True)
            y = y + jnp.where(row == i, y_i, 0.0)
        h_ref[...] = h
        y_ref[0, pl.ds(t0, G), :] = y.astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, T // G, group, ())


def selective_scan_fwd_pallas(u, delta, At, Bm, Cm, Dp, positions,
                              block_d: int = DEF_BLOCK_D,
                              chunk: int = DEF_CHUNK_T):
    """Shapes (already padded by ops.py): u, delta (B, L, Dm); At (N, Dm);
    Bm, Cm (B, L, N); Dp (1, Dm); positions (B, L, 1) i32.
    Returns (y (B, L, Dm), ckpts (B, L/T, N, Dm))."""
    Bz, L, Dm = u.shape
    N = At.shape[0]
    T, bd = chunk, block_d
    nL, nD = L // T, Dm // bd
    return pallas_call(
        _fwd_kernel,
        grid=(Bz, nD, nL),
        in_specs=[
            pl.BlockSpec((1, T, 1), lambda b, d, l: (b, l, 0)),    # pos
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, l, d)),   # u
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, l, d)),   # dt
            pl.BlockSpec((N, bd), lambda b, d, l: (0, d)),         # At
            pl.BlockSpec((1, T, N), lambda b, d, l: (b, l, 0)),    # Bm
            pl.BlockSpec((1, T, N), lambda b, d, l: (b, l, 0)),    # Cm
            pl.BlockSpec((1, bd), lambda b, d, l: (0, d)),         # Dp
        ],
        out_specs=[
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, l, d)),       # y
            pl.BlockSpec((1, 1, N, bd), lambda b, d, l: (b, l, 0, d)),  # ckpt
        ],
        out_shape=(jax.ShapeDtypeStruct((Bz, L, Dm), u.dtype),
                   jax.ShapeDtypeStruct((Bz, nL, N, Dm), f32)),
        scratch_shapes=[pltpu.VMEM((N, bd), f32)],
        compiler_params=_PARAMS,
    )(positions, u, delta, At, Bm, Cm, Dp)


# ---------------------------------------------------------------------------
# Mamba-1 backward kernels — reverse L-grid walk, per-chunk recompute
# ---------------------------------------------------------------------------

def _spread(x, N: int):
    """(G, w) rows → (G·N, w), each row repeated N times: a per-time-step
    value beside the N state rows of that step in a stacked (G·N, bd) tile."""
    G, w = x.shape
    return jnp.concatenate(
        [jnp.broadcast_to(x[i:i + 1], (N, w)) for i in range(G)], axis=0)


def _seg_sum(x, N: int):
    """(G·N, w) → (G, w): the sum over each step's N state rows."""
    return jnp.sum(x.reshape(x.shape[0] // N, N, x.shape[1]), axis=1)


def _lane_sums(x):
    """(R, bd) → (1, R): the sum over channels of each row, laid out along
    lanes. One transpose on the XLU, then plain adds over sublanes, in
    place of R cross-lane reductions."""
    return jnp.sum(x.T, axis=0, keepdims=True)


def _lane_sums_mxu(x):
    """``_lane_sums`` on the MXU: a ones matmul at f32 (``HIGHEST``), one
    8-row sublane tile of ones of which the first row is kept."""
    return _dot(jnp.ones((8, x.shape[1]), f32), x, _NT)[0:1]


def _bwd_group(T: int, nL: int) -> int:
    """Rows per group of the Mamba-1 backward: the most, up to 32, that
    divide the chunk and keep its lane-dense (T/G, G·N) dB, dC block in
    whole 8-row tiles (or the chunk is the whole sequence). Larger groups
    give the scheduler more of the bulk work to fill the walk's chain with:
    on a v5e the backward read 48.2 / 28.9 / 20.6 ms at G 8 / 16 / 32 at
    (8, 4096, 2048), with both channel sums on the XLU."""
    for G in (32, 16, 8, 4, 2, 1):
        if T % G == 0 and (nL == 1 or (T // G) % 8 == 0):
            return G


def _bwd_kernel(pos_ref, u_ref, dt_ref, At_ref, Bm_ref, Cm_ref, Dp_ref,
                ckpt_ref, dy_ref,
                du_ref, ddt_ref, dB_ref, dC_ref, dA_ref, dD_ref,
                hbuf_ref, g_ref, dA_acc, dD_acc, *, G):
    """Adjoint of one chunk. Same block shapes as forward plus:
    dy (1,T,bd) | du, ddt (1,T,bd) | dB, dC (1,1,T/G,G·N) per-(b,dblk)
    partials, lane-dense: row k holds steps [kG, kG+G) × N, (t, n) row-major |
    dA (1,N,bd), dD (1,1,bd) per-b partials |
    scratch: hbuf ((T+1)·N, bd) recomputed states, N rows per step;
    g (N,bd) adjoint carry; dA_acc (N,bd), dD_acc (1,bd).

    Reverse recurrence (paper §3.4 bwd: same Ā→0 rule):
      g_t ≡ dL/dh_t = C_t ⊗ dy_t + a_{t+1} · g_{t+1}
      da_t = g_t ⊙ h_{t-1}  →  dΔ += Σ_n da·a·A ;  dA += Σ_t da·a·Δ
      db_t = g_t            →  dB_t = Σ_d g·Δu ;  du += Δ·Σ_n g·B ; dΔ += u·Σ_n g·B
      dC_t = Σ_d dy_t ⊙ h_t ;  du += D·dy ;  dD += Σ_t dy·u

    Per group of G rows (``_bwd_group``) the serial walk only forms g_t and
    carries a_t·g_t; everything else is computed once for the group, after
    its walk and in the same loop body, on the stacked (G·N, bd) tiles of
    g, h and a: the N-row sums as adds, the channel sums of dC through one
    transpose and those of dB on the MXU, so that the XLU and the MXU share
    them, and du, dΔ stored as whole (G, bd) tiles.
    """
    T = u_ref.shape[1]
    N, bd = At_ref.shape

    @pl.when(pl.program_id(2) == 0)          # first step of the REVERSE walk
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        dA_acc[...] = jnp.zeros_like(dA_acc)
        dD_acc[...] = jnp.zeros_like(dD_acc)

    At = At_ref[...].astype(f32)
    Dp = Dp_ref[...].astype(f32)
    At_g = jnp.concatenate([At] * G, axis=0)             # (G·N, bd)

    # ---- recompute h trajectory within the chunk from the checkpoint ----
    hbuf_ref[pl.ds(0, N)] = ckpt_ref[0, 0]

    def fgroup(gi, _):
        t0 = _at(gi, G)
        r0 = _at(gi, G * N)
        dt, u, Bv, _, keep = _rows(pos_ref, u_ref, dt_ref, Bm_ref, Cm_ref,
                                   t0, G)
        BT, du = Bv.T, dt * u
        kf = jnp.where(keep, jnp.ones_like(dt), jnp.zeros_like(dt))
        h = hbuf_ref[pl.ds(r0, N)]
        for i in range(G):
            a = jnp.exp(dt[i:i + 1] * At) * kf[i:i + 1]
            h = a * h + BT[:, i:i + 1] * du[i:i + 1]
            hbuf_ref[pl.ds(r0 + (i + 1) * N, N)] = h
        return ()

    jax.lax.fori_loop(0, T // G, fgroup, ())

    # ---- reverse adjoint walk, then the group's adjoints in bulk ----
    def rgroup(k, _):
        gi = T // G - 1 - k
        t0 = _at(gi, G)
        r0 = _at(gi, G * N)
        dt, u, Bv, Cv, keep = _rows(pos_ref, u_ref, dt_ref, Bm_ref, Cm_ref,
                                    t0, G)
        dy = dy_ref[0, pl.ds(t0, G), :].astype(f32)
        CT = Cv.T
        kf = jnp.where(keep, jnp.ones_like(dt), jnp.zeros_like(dt))
        g = g_ref[...]
        gs = [None] * G
        for i in reversed(range(G)):
            g = CT[:, i:i + 1] * dy[i:i + 1] + g             # dL/dh_t
            gs[i] = g
            a = jnp.exp(dt[i:i + 1] * At) * kf[i:i + 1]
            g = a * g                                         # carry to t-1
        g_ref[...] = g
        g = jnp.concatenate(gs, axis=0)                      # (G·N, bd)
        h_prev = hbuf_ref[pl.ds(r0, G * N)]
        h = hbuf_ref[pl.ds(r0 + N, G * N)]
        da = g * h_prev * (jnp.exp(_spread(dt, N) * At_g) * _spread(kf, N))
        BT = Bv.T                                            # (N, G)
        Bcol = jnp.concatenate([BT[:, i:i + 1] for i in range(G)], axis=0)
        gB = _seg_sum(g * Bcol, N)                           # (G, bd)
        du = dt * u
        du_ref[0, pl.ds(t0, G), :] = (Dp * dy + dt * gB).astype(du_ref.dtype)
        ddt_ref[0, pl.ds(t0, G), :] = (_seg_sum(da * At_g, N) +
                                       u * gB).astype(ddt_ref.dtype)
        dB_ref[0, 0, pl.ds(gi, 1), :] = _lane_sums_mxu(
            g * _spread(du, N)).astype(dB_ref.dtype)
        dC_ref[0, 0, pl.ds(gi, 1), :] = _lane_sums(
            h * _spread(dy, N)).astype(dC_ref.dtype)
        dA_acc[...] += jnp.sum((da * _spread(dt, N)).reshape(G, N, bd),
                               axis=0)
        dD_acc[...] += jnp.sum(dy * u, axis=0, keepdims=True)
        return ()

    jax.lax.fori_loop(0, T // G, rgroup, ())

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        dA_ref[0] = dA_acc[...]
        dD_ref[0] = dD_acc[...]


def selective_scan_bwd_pallas(u, delta, At, Bm, Cm, Dp, positions, ckpts, dy,
                              block_d: int = DEF_BLOCK_D,
                              chunk: int = DEF_CHUNK_T):
    """Returns (du, ddelta, dB_partial, dC_partial, dA_partial (B,N,Dm),
    dD_partial (B,1,Dm)). The dB, dC partials hold (B, nD, L·N) values in
    (t, n) row-major order, lane-dense as (B, nD, L/G, G·N) (G the row
    group, ``_bwd_group``)."""
    Bz, L, Dm = u.shape
    N = At.shape[0]
    T, bd = chunk, block_d
    nL, nD = L // T, Dm // bd
    rev = lambda l: nL - 1 - l                 # walk the L dimension backwards
    G = _bwd_group(T, nL)
    kernel = functools.partial(_bwd_kernel, G=G)
    part = (L // G, G * N)
    part_spec = pl.BlockSpec((1, 1, T // G, G * N),
                             lambda b, d, l: (b, d, rev(l), 0))
    scratch = [
        pltpu.VMEM(((T + 1) * N, bd), f32),    # recomputed h
        pltpu.VMEM((N, bd), f32),              # adjoint carry
        pltpu.VMEM((N, bd), f32),              # dA accumulator
        pltpu.VMEM((1, bd), f32),              # dD accumulator
    ]
    out_shape = (
        jax.ShapeDtypeStruct((Bz, L, Dm), f32),       # du
        jax.ShapeDtypeStruct((Bz, L, Dm), f32),       # ddelta
        jax.ShapeDtypeStruct((Bz, nD) + part, f32),   # dB partials
        jax.ShapeDtypeStruct((Bz, nD) + part, f32),   # dC partials
        jax.ShapeDtypeStruct((Bz, N, Dm), f32),       # dA partials
        jax.ShapeDtypeStruct((Bz, 1, Dm), f32),       # dD partials
    )
    return pallas_call(
        kernel,
        grid=(Bz, nD, nL),
        in_specs=[
            pl.BlockSpec((1, T, 1), lambda b, d, l: (b, rev(l), 0)),    # pos
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, rev(l), d)),   # u
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, rev(l), d)),   # dt
            pl.BlockSpec((N, bd), lambda b, d, l: (0, d)),              # At
            pl.BlockSpec((1, T, N), lambda b, d, l: (b, rev(l), 0)),    # Bm
            pl.BlockSpec((1, T, N), lambda b, d, l: (b, rev(l), 0)),    # Cm
            pl.BlockSpec((1, bd), lambda b, d, l: (0, d)),              # Dp
            pl.BlockSpec((1, 1, N, bd), lambda b, d, l: (b, rev(l), 0, d)),
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, rev(l), d)),   # dy
        ],
        out_specs=[
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, rev(l), d)),
            pl.BlockSpec((1, T, bd), lambda b, d, l: (b, rev(l), d)),
            part_spec,
            part_spec,
            pl.BlockSpec((1, N, bd), lambda b, d, l: (b, 0, d)),
            pl.BlockSpec((1, 1, bd), lambda b, d, l: (b, 0, d)),
        ],
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_PARAMS,
    )(positions, u, delta, At, Bm, Cm, Dp, ckpts, dy)


# ---------------------------------------------------------------------------
# head-structured (scalar per-head decay) kernels
# ---------------------------------------------------------------------------

def _heads_tile(pos_ref, u_ref, dt_ref, Bm_ref, Cm_ref, A, t0, tt):
    """Per-subtile tensors of the heads schedules, all 2-D:
    dt (tt,1) | u (tt,P) | B, C (tt,N) | x = Δ·u (tt,P) |
    Lm (tt,tt) masked decay  Lm[i,j] = exp(Σ_{(j,i]} Δ·A)·[j ≤ i]·[no reset
    in (j,i]] | c (tt,1) decay from the subtile-entry state to row i (0
    after a reset) | wl (tt,1) = Lm[last, :] as a column."""
    sl = pl.ds(t0, tt)
    dt = dt_ref[0, 0, sl, :].astype(f32)
    u = u_ref[0, 0, sl, :].astype(f32)
    Bv = Bm_ref[0, sl, :].astype(f32)
    Cv = Cm_ref[0, sl, :].astype(f32)
    r = (pos_ref[0, sl, :] == 0).astype(f32)
    la = dt * A                                     # (tt, 1) log-decay
    incl, strict = _tri(tt), _tri(tt, strict=True)
    causal = incl > 0
    seg = _dot(incl, la * strict)                   # Σ_{k∈(j,i]} la_k
    cuts = _dot(incl, r * strict)                   # resets in (j, i]
    ok = causal & (cuts == 0)
    Lm = jnp.where(ok, jnp.exp(jnp.where(ok, seg, 0.0)), 0.0)
    s = _dot(incl, la)                              # Σ_{k≤i} la_k
    rid = _dot(incl, r)
    c = jnp.where(rid == 0, jnp.exp(s), 0.0)
    wl = jnp.where(rid == rid[tt - 1:tt], jnp.exp(s[tt - 1:tt] - s), 0.0)
    return dt, u, Bv, Cv, dt * u, Lm, c, wl


def _fwd_kernel_blocked_heads(pos_ref, u_ref, dt_ref, A_ref, Bm_ref, Cm_ref,
                              Dp_ref, y_ref, ckpt_ref, h_ref, *, sub_t):
    """One (b, head, l-chunk) grid step, scalar per-head decay, state form.

    pos (1,T,1) i32 | u (1,1,T,P) | dt (1,1,T,1) | A, Dp (1,1,1) scalars |
    Bm, Cm (1,T,N) | y (1,1,T,P) | ckpt (1,1,1,P,N) | h scratch (P,N) f32.

    Per subtile the in-chunk states are formed one state column n at a
    time, each a single (Tt, Tt) @ (Tt, P) matmul:

        h[:, :, n] = Lm @ (x ⊙ B[:, n]) + c ⊗ h_in[:, n]
        y          = Σ_n C[:, n] ⊙ h[:, :, n] + D·u
    """
    T = u_ref.shape[2]
    P, N = h_ref.shape
    tt = sub_t

    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    ckpt_ref[0, 0, 0] = h_ref[...]
    A = A_ref[0].astype(f32)                           # (1, 1)
    Dp = Dp_ref[0].astype(f32)
    rowN = _rowsel(N, P)

    def sub(si, _):
        t0 = _at(si, tt)
        _, u, Bv, Cv, x, Lm, c, _ = _heads_tile(
            pos_ref, u_ref, dt_ref, Bm_ref, Cm_ref, A, t0, tt)
        hT = h_ref[...].T                              # (N, P)
        y = Dp * u
        hT_new = jnp.zeros_like(hT)
        for n in range(N):
            h = _dot(Lm, x * Bv[:, n:n + 1]) + c * hT[n:n + 1]   # (tt, P)
            y = y + Cv[:, n:n + 1] * h
            hT_new = hT_new + jnp.where(rowN == n, h[tt - 1:tt], 0.0)
        h_ref[...] = hT_new.T
        y_ref[0, 0, pl.ds(t0, tt), :] = y.astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, T // tt, sub, ())


def _fwd_kernel_blocked_heads_dual(pos_ref, u_ref, dt_ref, A_ref, Bm_ref,
                                   Cm_ref, Dp_ref, y_ref, ckpt_ref, h_ref, *,
                                   sub_t):
    """Dual-form twin of ``_fwd_kernel_blocked_heads`` (same grid, block
    shapes, carry semantics, and ckpt output — so one backward kernel
    serves both). Per subtile the masked decay folds into the (Tt, Tt)
    C·Bᵀ Gram matrix and the outputs come straight from matmuls, without
    forming the (Tt, P, N) in-chunk states:

        y        = (Lm ⊙ C Bᵀ) @ x  +  c ⊙ (C @ h_inᵀ)  + D·u
        h_new    = (wl ⊙ x)ᵀ @ B  +  c[last]·h_in

    FLOPs Tt²·(N + P) + Tt·P·N vs the state form's Tt²·P·N — the measured
    winner when dh ≫ Tt (core/ssm.py has the XLA math).
    """
    T = u_ref.shape[2]
    tt = sub_t

    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    ckpt_ref[0, 0, 0] = h_ref[...]
    A = A_ref[0].astype(f32)
    Dp = Dp_ref[0].astype(f32)

    def sub(si, _):
        t0 = _at(si, tt)
        _, u, Bv, Cv, x, Lm, c, wl = _heads_tile(
            pos_ref, u_ref, dt_ref, Bm_ref, Cm_ref, A, t0, tt)
        h_in = h_ref[...]                                         # (P, N)
        y = _dot(Lm * _dot(Cv, Bv, _NT), x) + \
            c * _dot(Cv, h_in, _NT) + Dp * u
        h_ref[...] = _dot(wl * x, Bv, _TN) + _last(c) * h_in
        y_ref[0, 0, pl.ds(t0, tt), :] = y.astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, T // tt, sub, ())


_HEADS_FWD_KERNELS = {"blocked_heads": _fwd_kernel_blocked_heads,
                      "blocked_heads_dual": _fwd_kernel_blocked_heads_dual}


def selective_scan_heads_fwd_pallas(u, delta, Ah, Bm, Cm, Dp, positions,
                                    chunk: int = DEF_CHUNK_T,
                                    schedule: str = "blocked_heads",
                                    sub_t: Optional[int] = None):
    """Head-major shapes (already padded/transposed by ops.py):
    u (B, H, L, P); delta (B, H, L, 1); Ah, Dp (H, 1, 1); Bm, Cm (B, L, N);
    positions (B, L, 1) i32. ``schedule``: 'blocked_heads' (state form) |
    'blocked_heads_dual' (C·Bᵀ attention-like form; same ckpt contract).
    Returns (y (B, H, L, P), ckpts (B, H, L/T, P, N))."""
    Bz, H, L, P = u.shape
    N = Bm.shape[-1]
    T = chunk
    nL = L // T
    if schedule not in _HEADS_FWD_KERNELS:
        raise ValueError(f"unknown heads schedule {schedule!r}")
    kernel = functools.partial(_HEADS_FWD_KERNELS[schedule],
                               sub_t=_pick_subtile(T, sub_t))
    return pallas_call(
        kernel,
        grid=(Bz, H, nL),
        in_specs=[
            pl.BlockSpec((1, T, 1), lambda b, h, l: (b, l, 0)),        # pos
            pl.BlockSpec((1, 1, T, P), lambda b, h, l: (b, h, l, 0)),  # u
            pl.BlockSpec((1, 1, T, 1), lambda b, h, l: (b, h, l, 0)),  # dt
            pl.BlockSpec((1, 1, 1), lambda b, h, l: (h, 0, 0)),        # A
            pl.BlockSpec((1, T, N), lambda b, h, l: (b, l, 0)),        # Bm
            pl.BlockSpec((1, T, N), lambda b, h, l: (b, l, 0)),        # Cm
            pl.BlockSpec((1, 1, 1), lambda b, h, l: (h, 0, 0)),        # Dp
        ],
        out_specs=[
            pl.BlockSpec((1, 1, T, P), lambda b, h, l: (b, h, l, 0)),  # y
            pl.BlockSpec((1, 1, 1, P, N),
                         lambda b, h, l: (b, h, l, 0, 0)),             # ckpt
        ],
        out_shape=(jax.ShapeDtypeStruct((Bz, H, L, P), u.dtype),
                   jax.ShapeDtypeStruct((Bz, H, nL, P, N), f32)),
        scratch_shapes=[pltpu.VMEM((P, N), f32)],
        compiler_params=_PARAMS,
    )(positions, u, delta, Ah, Bm, Cm, Dp)


def _bwd_kernel_blocked_heads(pos_ref, u_ref, dt_ref, A_ref, Bm_ref, Cm_ref,
                              Dp_ref, ckpt_ref, dy_ref,
                              du_ref, ddt_ref, dB_ref, dC_ref, dA_ref,
                              dD_ref, hs_ref, g_ref, dA_acc, dD_acc, *,
                              sub_t):
    """Adjoint of one (b, head, l-chunk), scalar per-head decay, in the
    dual (matmul) form — no (T, P, N) trajectory is stored. The subtile
    entry states are recomputed from the chunk checkpoint into hs
    (T/Tt, P, N); then, walking the subtiles in reverse with the adjoint
    carry G = dL/dh at the subtile's end, per subtile (M = Lm ⊙ C Bᵀ,
    Q = dy xᵀ):

        dx   = Mᵀ dy + wl ⊙ (B Gᵀ)            du = Δ·dx + D·dy
        dB   = (Lm ⊙ Q)ᵀ C + wl ⊙ (x G)
        dC   = (Lm ⊙ Q) B + c ⊙ (dy h_in)
        dla_m = Σ_{k≥m>j} (M⊙Q)[k,j] + Σ_{j<m} wl_j x_j·(G B_j)
               + Σ_{k≥m} c_k dy_k·(h_in C_k) + c_last (h_in : G)
        dΔ   = dla·A + Σ_p u·dx ;   dA += Σ dla·Δ ;   dD += Σ dy·u
        G   ← (c ⊙ dy)ᵀ C + c_last·G          (dL/dh at the subtile entry)
    """
    T = u_ref.shape[2]
    tt = sub_t
    nsub = T // tt

    @pl.when(pl.program_id(2) == 0)          # first step of the REVERSE walk
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        dA_acc[...] = jnp.zeros_like(dA_acc)
        dD_acc[...] = jnp.zeros_like(dD_acc)

    A = A_ref[0].astype(f32)
    Dp = Dp_ref[0].astype(f32)
    incl = _tri(tt)
    strict = _tri(tt, strict=True)
    upper = _tri(tt, upper=True)                       # [k ≥ m]
    after = _tri(tt, strict=True, upper=True)          # [j < m]
    ones = jnp.ones((tt, 1), f32)

    # ---- recompute the subtile-entry states ----
    hs_ref[0] = ckpt_ref[0, 0, 0]

    def fsub(si, _):
        _, _, Bv, _, x, _, c, wl = _heads_tile(
            pos_ref, u_ref, dt_ref, Bm_ref, Cm_ref, A, _at(si, tt), tt)
        hs_ref[si + 1] = _dot(wl * x, Bv, _TN) + _last(c) * hs_ref[si]
        return ()

    jax.lax.fori_loop(0, nsub - 1, fsub, ())

    # ---- reverse walk over subtiles ----
    def rsub(k, _):
        si = nsub - 1 - k
        t0 = _at(si, tt)
        sl = pl.ds(t0, tt)
        dt, u, Bv, Cv, x, Lm, c, wl = _heads_tile(
            pos_ref, u_ref, dt_ref, Bm_ref, Cm_ref, A, t0, tt)
        dy = dy_ref[0, 0, sl, :].astype(f32)                      # (tt, P)
        h0 = hs_ref[si]                                           # (P, N)
        G = g_ref[...]
        M = Lm * _dot(Cv, Bv, _NT)
        Q = _dot(dy, x, _NT)
        LQ = Lm * Q
        BG = _dot(Bv, G, _NT)                                     # (tt, P)
        dx = _dot(M, dy, _TN) + wl * BG
        dB = _dot(LQ, Cv, _TN) + wl * _dot(x, G)
        dC = _dot(LQ, Bv) + c * _dot(dy, h0)
        c_last = _last(c)
        dla = (_dot(_dot(M * Q, after) * incl, ones, _TN) +
               _dot(strict, wl * jnp.sum(x * BG, axis=1, keepdims=True)) +
               _dot(upper, c * jnp.sum(dy * _dot(Cv, h0, _NT), axis=1,
                                       keepdims=True)) +
               c_last * jnp.sum(h0 * G, keepdims=True))
        du_ref[0, 0, sl, :] = (dt * dx + Dp * dy).astype(du_ref.dtype)
        ddt_ref[0, 0, sl, :] = (dla * A + jnp.sum(
            u * dx, axis=1, keepdims=True)).astype(ddt_ref.dtype)
        dB_ref[0, 0, sl, :] = dB.astype(dB_ref.dtype)
        dC_ref[0, 0, sl, :] = dC.astype(dC_ref.dtype)
        dA_acc[...] += jnp.sum(dla * dt, keepdims=True)
        dD_acc[...] += jnp.sum(dy * u, keepdims=True)
        g_ref[...] = _dot(c * dy, Cv, _TN) + c_last * G
        return ()

    jax.lax.fori_loop(0, nsub, rsub, ())

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        dA_ref[0, 0] = dA_acc[...]
        dD_ref[0, 0] = dD_acc[...]


def selective_scan_heads_bwd_pallas(u, delta, Ah, Bm, Cm, Dp, positions,
                                    ckpts, dy,
                                    chunk: int = DEF_CHUNK_T,
                                    sub_t: Optional[int] = None):
    """Head-major shapes as in the forward. Returns (du (B,H,L,P),
    ddelta (B,H,L,1), dB_partial (B,H,L,N), dC_partial (B,H,L,N),
    dA_partial (B,H,1,1), dD_partial (B,H,1,1)).

    Serves BOTH forward schedules: the adjoint math is schedule-independent
    and the dual forward writes the same chunk-entry ckpts."""
    Bz, H, L, P = u.shape
    N = Bm.shape[-1]
    T = chunk
    nL = L // T
    tt = _pick_subtile(T, sub_t)
    rev = lambda l: nL - 1 - l                 # walk the L dimension backwards
    kernel = functools.partial(_bwd_kernel_blocked_heads, sub_t=tt)
    scratch = [
        pltpu.VMEM((T // tt, P, N), f32),      # subtile-entry states
        pltpu.VMEM((P, N), f32),               # adjoint carry G
        pltpu.VMEM((1, 1), f32),               # per-head dA accumulator
        pltpu.VMEM((1, 1), f32),               # per-head dD accumulator
    ]
    out_shape = (
        jax.ShapeDtypeStruct((Bz, H, L, P), f32),     # du
        jax.ShapeDtypeStruct((Bz, H, L, 1), f32),     # ddelta
        jax.ShapeDtypeStruct((Bz, H, L, N), f32),     # dB partials
        jax.ShapeDtypeStruct((Bz, H, L, N), f32),     # dC partials
        jax.ShapeDtypeStruct((Bz, H, 1, 1), f32),     # dA partials
        jax.ShapeDtypeStruct((Bz, H, 1, 1), f32),     # dD partials
    )
    return pallas_call(
        kernel,
        grid=(Bz, H, nL),
        in_specs=[
            pl.BlockSpec((1, T, 1), lambda b, h, l: (b, rev(l), 0)),   # pos
            pl.BlockSpec((1, 1, T, P), lambda b, h, l: (b, h, rev(l), 0)),
            pl.BlockSpec((1, 1, T, 1), lambda b, h, l: (b, h, rev(l), 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, l: (h, 0, 0)),        # A
            pl.BlockSpec((1, T, N), lambda b, h, l: (b, rev(l), 0)),   # Bm
            pl.BlockSpec((1, T, N), lambda b, h, l: (b, rev(l), 0)),   # Cm
            pl.BlockSpec((1, 1, 1), lambda b, h, l: (h, 0, 0)),        # Dp
            pl.BlockSpec((1, 1, 1, P, N),
                         lambda b, h, l: (b, h, rev(l), 0, 0)),        # ckpt
            pl.BlockSpec((1, 1, T, P), lambda b, h, l: (b, h, rev(l), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, T, P), lambda b, h, l: (b, h, rev(l), 0)),
            pl.BlockSpec((1, 1, T, 1), lambda b, h, l: (b, h, rev(l), 0)),
            pl.BlockSpec((1, 1, T, N), lambda b, h, l: (b, h, rev(l), 0)),
            pl.BlockSpec((1, 1, T, N), lambda b, h, l: (b, h, rev(l), 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, h, l: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, h, l: (b, h, 0, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_PARAMS,
    )(positions, u, delta, Ah, Bm, Cm, Dp, ckpts, dy)
