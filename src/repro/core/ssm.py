"""Selective scan with PackMamba segment resets — XLA path.

One *head-structured* state-space interface serves both Mamba generations.
The general layout is ``(B, L, H, dh)`` inputs with state ``(B, H, dh, N)``:

  * **Mamba-2 / SSD** — per-head *scalar* decay ``A: (H,)``:

        Ā[b,l,h]      = exp(Δ[b,l,h] · A[h])     A = -exp(A_log)  (real < 0)
        B̄x[b,l,h,p,n] = Δ[b,l,h] · B[b,l,n] · u[b,l,h,p]
        h_t = Ā_t · h_{t-1} + B̄x_t               (per (b, h); scalar decay)
        y[b,l,h,p] = Σ_n C[b,l,n] · h[b,l,h,p,n] + D[h] · u[b,l,h,p]

    With scalar decay the blocked schedule's cumulative-decay matrix is one
    (T, T) matrix per head, so a whole chunk evaluates as a single
    (T, T) · (T, dh·N) matmul — see ``selective_scan_heads``.

  * **Mamba-1** — the degenerate case ``H = d_inner, dh = 1`` with
    *per-channel* decay ``A: (D, N)`` (paper eq. 2a/2b, ZOH-for-A /
    Euler-for-B):

        Ā[b,l,d,n] = exp(Δ[b,l,d] · A[d,n])
        B̄x[b,l,d,n] = Δ[b,l,d] · B[b,l,n] · u[b,l,d]
        y[b,l,d] = Σ_n C[b,l,n] · h[b,l,d,n] + D[d] · u[b,l,d]

    ``selective_scan`` keeps the historical (B, L, D) surface and routes
    through ``selective_scan_heads`` with dh = 1.

PackMamba (§3.4): wherever position_indices == 0, Ā → 0 — state reset at the
start of each packed sequence. In serial form this equals Δ→∞ state
forgetting that selective SSMs already support (paper eq. 2a remark); in
parallel form the reset composes with the associative combine (see
core/scan.py docstring).

Serving handoff (``collect_ends``): because resets make the state at index
``e`` depend only on tokens of ``e``'s own segment, the final state of EVERY
packed segment is just the state trajectory sampled at that segment's last
token. ``collect_ends (B, S)`` asks the evaluators to also return those
samples ``h_ends (B, S, …)`` (−1 entries = absent segment → zeros). The
blocked schedules gather them from the in-chunk state slice they already
compute — one O(B·S·state) gather per chunk, no extra scan passes and still
no (B, L, …, N) materialization.

This module is the XLA path; the Pallas TPU kernels live in
kernels/selective_scan.py and match this to numerical tolerance (the
per-step walk for per-channel decay, ``'blocked_heads'`` for per-head
scalar decay).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.scan import segmented_scan, scan_step, gather_state_ends
from repro.core.scan import _combine as _scan_combine

_MATMUL_CHUNK_CAP = 32    # blocked/matmul intra: bounds the T²·D·N operand
_HEADS_CHUNK_CAP = 64     # blocked heads (quad): bounds the (T, T, H) decay
#   matrix and the T× FLOP multiplier of the single-matmul step (SSD picks
#   T ≈ dh so the (T,T)·(T,dh·N) matmul stays square-ish)
_HEADS_DUAL_CHUNK_CAP = 128  # dual form: the T² term is only (dh + N) wide,
#   so a larger T pays off — but the (B, T, T, H) Gram/decay matrices still
#   grow as T², hence a cap of their own
# The caps bound worst-case memory whatever chunk the caller asks for.


def selective_scan(u: jnp.ndarray, delta: jnp.ndarray, A: jnp.ndarray,
                   B: jnp.ndarray, C: jnp.ndarray,
                   D: Optional[jnp.ndarray] = None,
                   positions: Optional[jnp.ndarray] = None,
                   h0: Optional[jnp.ndarray] = None,
                   method: str = "chunked", chunk: int = 256,
                   return_state: bool = False,
                   compute_dtype=None, intra: Optional[str] = None,
                   collect_ends: Optional[jnp.ndarray] = None):
    """Mamba-1 surface: u,delta: (B,L,D); A: (D,N); B,C: (B,L,N); D: (D,).

    The degenerate head-structured case H = D, dh = 1 — dispatches through
    ``selective_scan_heads`` (the unified state-space interface).

    positions: (B,L) int32 — PackMamba position indices (reset where == 0).
    h0: (B, D, N) initial state (for split-pack state carry / decode chunking).
    compute_dtype: recurrence dtype (default f32; bf16 halves scan traffic).
    intra: method='blocked' only — in-chunk evaluator ('matmul' | 'assoc';
    default picks 'matmul' on TPU, 'assoc' elsewhere — see _blocked_ssm).
    collect_ends: (B, S) int32 segment-end indices (−1 = absent) — per-
    segment serving handoff (module docstring).
    Returns y (B, L, D) [, h_last (B, D, N)] [, h_ends (B, S, D, N)].
    """
    out = selective_scan_heads(
        u[..., None], delta, A, B, C, D, positions=positions,
        h0=None if h0 is None else h0[:, :, None, :],
        method=method, chunk=chunk, return_state=return_state,
        compute_dtype=compute_dtype, intra=intra,
        collect_ends=collect_ends)
    if not (return_state or collect_ends is not None):
        return out[..., 0]
    out = list(out)
    out[0] = out[0][..., 0]                          # y: drop dh = 1
    for i in range(1, len(out)):
        out[i] = out[i][..., 0, :]                   # states: (…, dh=1, N)
    return tuple(out)


def selective_scan_heads(u: jnp.ndarray, delta: jnp.ndarray, A: jnp.ndarray,
                         B: jnp.ndarray, C: jnp.ndarray,
                         D: Optional[jnp.ndarray] = None,
                         positions: Optional[jnp.ndarray] = None,
                         h0: Optional[jnp.ndarray] = None,
                         method: str = "blocked", chunk: int = 64,
                         return_state: bool = False,
                         compute_dtype=None, intra: Optional[str] = None,
                         collect_ends: Optional[jnp.ndarray] = None):
    """Unified head-structured state-space interface (module docstring).

    u: (B, L, H, dh); delta: (B, L, H); B, C: (B, L, N) (shared across the
    heads of a group); D: (H,) skip; positions: (B, L) int32 (reset where
    == 0); h0: (B, H, dh, N); collect_ends: (B, S) int32 segment-end
    indices (−1 = absent) for the per-segment serving handoff.

    ``A`` selects the variant:
      * (H,)   — Mamba-2/SSD scalar per-head decay. ``method``:
                 'blocked' (single-matmul chunk evaluation — the hot path)
                 | 'sequential' (reference / short L). ``intra`` picks the
                 blocked in-chunk form: 'quad' (state-form dec @ b, the
                 default) | 'dual' (C·Bᵀ attention-like contraction straight
                 to outputs — wins when dh ≫ T; see _blocked_ssm_heads).
      * (H, N) — Mamba-1 per-(channel, state) decay; requires dh == 1 and
                 accepts every per-channel ``method`` ('blocked' | 'chunked'
                 | 'fused_seq' | 'sequential' | 'associative', plus
                 ``intra`` ∈ ('matmul', 'assoc') for 'blocked').

    Returns y (B, L, H, dh) [, h_last (B, H, dh, N)]
    [, h_ends (B, S, H, dh, N)].
    """
    Bsz, L, H, P = u.shape
    if A.ndim == 2:
        # Mamba-1 degenerate case: fold dh into the channel axis and run the
        # per-channel evaluators.
        if P != 1:
            raise ValueError(
                f"per-channel decay A{A.shape} requires dh == 1, got {P}")
        y, h_last, h_ends = _selective_scan_channels(
            u[..., 0], delta, A, B, C, D, positions,
            None if h0 is None else h0[:, :, 0, :],
            method, chunk, compute_dtype, intra, collect_ends)
        return _pack_scan_out(
            y[..., None], h_last[:, :, None, :],
            None if h_ends is None else h_ends[:, :, :, None, :],
            return_state, collect_ends)
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None else \
        jnp.promote_types(u.dtype, jnp.float32)
    if method == "blocked":
        y, h_last, h_ends = _blocked_ssm_heads(
            u, delta, A, B, C, D, positions, h0, cdt, chunk, collect_ends,
            intra=intra)
    elif method == "sequential":
        y, h_last, h_ends = _seq_scan_heads(
            u, delta, A, B, C, D, positions, h0, cdt, collect_ends)
    else:
        raise ValueError(f"unknown scalar-decay scan method {method!r}")
    return _pack_scan_out(y, h_last, h_ends, return_state, collect_ends)


def _pack_scan_out(y, h_last, h_ends, return_state, collect_ends):
    out = (y,)
    if return_state:
        out += (h_last,)
    if collect_ends is not None:
        out += (h_ends,)
    return out[0] if len(out) == 1 else out


_gather_ends = gather_state_ends


def _selective_scan_channels(u, delta, A, B, C, D, positions, h0,
                             method, chunk, compute_dtype, intra,
                             collect_ends=None):
    """Per-channel (Mamba-1) evaluator family. u,delta: (B,L,D); A: (D,N).

    Returns (y, h_last, h_ends|None) — packed by the dispatcher."""
    Bsz, L, Dm = u.shape
    N = A.shape[-1]
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None else \
        jnp.promote_types(u.dtype, jnp.float32)     # scan state dtype
    if method == "fused_seq":
        # §Perf iteration: fold y = C·h into a single sequential scan so the
        # (B, L, D, N) decay/h trajectories are NEVER materialized — HBM
        # traffic drops from O(L·D·N·log chunk) to O(L·D·N) carry round-trips
        # + O(L·D) outputs. (The Pallas kernel is the real TPU answer; this
        # is its closest pure-XLA analogue.)
        return _fused_seq_scan(u, delta, A, B, C, D, positions, h0,
                               cdt, collect_ends)
    if method == "blocked":
        # SSD-style block-parallel schedule: also never materializes
        # (B, L, D, N), and replaces the elementwise recurrence with
        # matmul-shaped contractions (see core/scan.py docstring).
        return _blocked_ssm(u, delta, A, B, C, D, positions, h0,
                            cdt, chunk, intra, collect_ends)
    delta_f = delta.astype(cdt)
    # decay a = exp(Δ·A): (B, L, D, N)
    a = jnp.exp(delta_f[..., None] * A.astype(cdt))
    # b-term = Δ·B·u: (B, L, D, N)
    bterm = (delta_f * u.astype(cdt))[..., None] * B.astype(cdt)[:, :, None, :]
    reset = (positions == 0) if positions is not None else None
    h, h_last = segmented_scan(a, bterm, reset=reset, h0=h0,
                               method=method, chunk=chunk)
    y = jnp.einsum("bldn,bln->bld", h, C.astype(cdt))
    if D is not None:
        y = y + D.astype(cdt) * u.astype(cdt)
    y = y.astype(u.dtype)
    h_ends = _gather_ends(h, collect_ends) if collect_ends is not None \
        else None
    return y, h_last, h_ends


def _blocked_ssm(u, delta, A, B, C, D, positions, h0, cdt,
                 chunk, intra=None, collect_ends=None):
    """Block-parallel (SSD-style) selective scan — the fused hot path.

    The schedule: partition L into chunks of length T, evaluate the whole
    reset-masked in-chunk operator at once, and carry only the (B, D, N)
    state across the O(L/T) chunk boundary recurrence. Per chunk
    (structured-state-space duality, Gu & Dao, specialized to Mamba-1's
    (D, N) diagonal decay and PackMamba resets):

        M[i,j]   = Π_{j<k≤i} Ā_k  = exp(s_i − s_j)   masked to j ≤ i AND no
                   reset in (j, i]    (s = in-chunk cumsum of Δ·A)
        h_i      = Σ_j M[i,j]·(Δ·B·u)_j  +  1[no reset ≤ i]·exp(s_i)·h_in
        y_i      = C_i · h_i  (+ D·u)

    Only the current chunk's tensors are ever live — never the (B, L, D, N)
    decay/input trajectory the ``chunked`` method materializes up front —
    and y = C·h is fused into the chunk body, so HBM sees only the
    (B, L, D) output plus O(B·L·(D+N)) raw inputs (the chunk body is
    checkpointed, so backward residuals stay at the raw inputs too).

    ``intra`` selects how the in-chunk operator is evaluated:
      * ``"matmul"`` — build M explicitly and contract h = M @ b as an
        einsum. With Mamba-1's per-(channel, state) decay M differs for
        every (b, d, n), so the einsum is a batched matrix–vector product:
        no MXU shape, and the VPU/EUP pays T exponentials and T
        multiply-adds per state element where the recurrence pays one of
        each — the carry chain shrinks by T, the work grows by T. The
        default when running on TPU, where the train step's scan runs the
        Pallas kernel instead (models/blocks.py::_scan_backend) and this
        form serves only the state-collecting prefills. Peak per-chunk
        intermediate is the (B, T, T, D, N) masked decay (s_i − s_j ≤ 0
        for unmasked pairs since A < 0, Δ ≥ 0; masked pairs are clamped
        before the exp, so no overflow anywhere).
      * ``"assoc"`` — evaluate the same masked operator with an in-chunk
        associative tree (log₂T passes of elementwise combines). No matrix
        units to feed on CPU, so this is the default there; it keeps the
        schedule's fusion/memory wins (≈2-3× faster than ``chunked`` at
        L ≥ 1024 on CPU — see benchmarks/run.py fig2) without the T×
        element-op blowup that only an MXU makes free.
    Both evaluate the identical operator: results match ``sequential`` to
    f32 tolerance either way.
    """
    if intra is None:
        intra = "matmul" if jax.default_backend() == "tpu" else "assoc"
    if intra not in ("matmul", "assoc"):
        raise ValueError(f"unknown blocked intra mode {intra!r}")
    Bsz, L, Dm = u.shape
    N = A.shape[-1]
    T = min(chunk, L)
    if intra == "matmul":
        # the (B, T, T, D, N) contraction operand grows as T²·D·N: an
        # uncapped scan_chunk (256) would dwarf the (B, L, D, N) buffer
        # this schedule exists to avoid.
        T = min(T, _MATMUL_CHUNK_CAP)
    A32 = A.astype(cdt)
    reset = (positions == 0) if positions is not None else \
        jnp.zeros((Bsz, L), bool)
    pad = (-L) % T
    if pad:
        # Δ=0 ⇒ decay 1 / b-term 0 (state carried), no reset: identity steps
        padw = [(0, 0), (0, pad)]
        u = jnp.pad(u, padw + [(0, 0)])
        delta = jnp.pad(delta, padw + [(0, 0)])
        B = jnp.pad(B, padw + [(0, 0)])
        C = jnp.pad(C, padw + [(0, 0)])
        reset = jnp.pad(reset, padw)
    Lp = u.shape[1]
    nc = Lp // T
    if h0 is None:
        h0 = jnp.zeros((Bsz, Dm, N), cdt)
    h0 = h0.astype(cdt)
    tril = jnp.tril(jnp.ones((T, T), bool))
    collect = collect_ends is not None
    nseg = collect_ends.shape[1] if collect else 0

    @jax.checkpoint
    def chunk_step(carry, xs):
        h_in, acc = carry
        uc, dc, Bc, Cc, rc, ci = xs      # (B,T,Dm) ×2, (B,T,N) ×2, (B,T), ()
        d32 = dc.astype(cdt)
        bterm = (d32 * uc.astype(cdt))[..., None] * \
            Bc.astype(cdt)[:, :, None, :]               # (B,T,Dm,N)
        if intra == "matmul":
            la = d32[..., None] * A32                   # (B,T,Dm,N) log decay
            s = jnp.cumsum(la, axis=1)
            rid = jnp.cumsum(rc.astype(jnp.int32), axis=1)   # resets ≤ i
            m = (rid[:, :, None] == rid[:, None, :]) & tril[None]  # (B,T,T)
            mm = m[..., None, None]
            diff = s[:, :, None] - s[:, None, :]        # (B,T,T,Dm,N)
            dec = jnp.where(mm, jnp.exp(jnp.where(mm, diff, 0.0)), 0.0)
            h = jnp.einsum("bijdn,bjdn->bidn", dec, bterm)
            cin = jnp.where((rid == 0)[..., None, None], jnp.exp(s), 0.0)
            h = h + cin * h_in[:, None]
        else:
            a = jnp.exp(d32[..., None] * A32)           # (B,T,Dm,N)
            a = jnp.where(rc[..., None, None], 0.0, a)  # PackMamba reset
            Acum, Bcum = jax.lax.associative_scan(_scan_combine, (a, bterm),
                                                  axis=1)
            h = Acum * h_in[:, None] + Bcum             # Acum: carry decay,
            #   zeroed past an in-chunk reset since a→0 poisons its products
        if collect:
            # serving handoff: sample the in-chunk states (already live in
            # both intra modes) at the segment ends that fall in this chunk
            local = collect_ends - ci * T               # (B, S)
            ok = (local >= 0) & (local < T)
            lcl = jnp.clip(local, 0, T - 1)[..., None, None]
            sel = jnp.take_along_axis(
                h, jnp.broadcast_to(lcl, (Bsz, nseg, Dm, N)), axis=1)
            acc = acc + jnp.where(ok[..., None, None], sel, 0)
        y = jnp.einsum("bidn,bin->bid", h, Cc.astype(cdt))
        return (h[:, -1], acc), y

    xs = tuple(jnp.moveaxis(x.reshape((Bsz, nc, T) + x.shape[2:]), 1, 0)
               for x in (u, delta, B, C, reset))
    acc0 = jnp.zeros((Bsz, nseg, Dm, N), cdt) if collect else \
        jnp.zeros((), cdt)
    (h_last, h_ends), ys = jax.lax.scan(chunk_step, (h0, acc0),
                                        xs + (jnp.arange(nc),))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, Lp, Dm)[:, :L]
    if D is not None:
        y = y + D.astype(cdt) * u[:, :L].astype(cdt)
    y = y.astype(u.dtype)
    return y, h_last, (h_ends if collect else None)


def _fused_seq_scan(u, delta, A, B, C, D, positions, h0, cdt,
                    collect_ends=None):
    Bsz, L, Dm = u.shape
    N = A.shape[-1]
    A32 = A.astype(cdt)
    reset = (positions == 0) if positions is not None else \
        jnp.zeros((Bsz, L), bool)
    if h0 is None:
        h0 = jnp.zeros((Bsz, Dm, N), cdt)
    collect = collect_ends is not None
    nseg = collect_ends.shape[1] if collect else 0

    def step(carry, xs):
        h, acc = carry
        u_t, d_t, B_t, C_t, r_t, t = xs
        d32 = d_t.astype(cdt)
        a_t = jnp.exp(d32[..., None] * A32)               # (B, Dm, N)
        a_t = jnp.where(r_t[:, None, None], 0.0, a_t)
        h = a_t * h + (d32 * u_t.astype(cdt))[..., None] * \
            B_t.astype(cdt)[:, None, :]
        if collect:
            ok = (collect_ends == t)[..., None, None]     # (B, S, 1, 1)
            acc = acc + jnp.where(ok, h[:, None], 0)
        y_t = jnp.einsum("bdn,bn->bd", h, C_t.astype(cdt))
        return (h, acc), y_t

    xs = (jnp.moveaxis(u, 1, 0), jnp.moveaxis(delta, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0),
          jnp.moveaxis(reset, 1, 0), jnp.arange(L))
    acc0 = jnp.zeros((Bsz, nseg, Dm, N), cdt) if collect else \
        jnp.zeros((), cdt)
    (h_last, h_ends), ys = jax.lax.scan(step, (h0, acc0), xs)
    y = jnp.moveaxis(ys, 0, 1)
    if D is not None:
        y = y + D.astype(cdt) * u.astype(cdt)
    y = y.astype(u.dtype)
    return y, h_last, (h_ends if collect else None)


# ---------------------------------------------------------------------------
# head-structured (scalar per-head decay) evaluators — Mamba-2 / SSD
# ---------------------------------------------------------------------------

def _blocked_ssm_heads(u, delta, A, B, C, D, positions, h0,
                       cdt, chunk, collect_ends=None, intra=None):
    """Block-parallel schedule, per-head scalar decay — the SSD hot path.

    The same schedule as ``_blocked_ssm`` but the decay depends only on
    (b, l, h), so per chunk of length T the masked cumulative-decay matrix

        dec[i,j] = exp(s_i − s_j)·[j ≤ i]·[no reset in (j, i]]   (s = cumsum Δ·A)

    is a single (T, T) matrix per (b, h) — NOT (T, T, D, N). ``intra``
    selects how the in-chunk operator is evaluated against it:

      * ``"quad"`` (default, ``None``) — the state form: every in-chunk
        state is produced by ONE matmul-shaped contraction

          h[i, p, n] = Σ_j dec[i,j] · (Δ·u ⊗ B)[j, p, n]    ((T,T)·(T,dh·N))

        per head, with y = C·h fused in the chunk body. T²·dh·N FLOPs per
        head per chunk; the in-chunk (T, dh, N) states are live (and are
        what ``collect_ends`` samples).
      * ``"dual"`` — the attention-like form (structured-state-space
        duality, the 'quadratic mode' of SSD): contract straight to outputs
        through the (T, T) Gram matrix

          G[i,j]    = dec[i,j] · (C_i · B_j)
          y[i,p]    = Σ_j G[i,j] · (Δ·u)[j,p]  +  cin_i · (C_i · h_in)[p]

        plus one decay-weighted reduction for the chunk-final carry state.
        T²·(dh + N) + T·dh·N FLOPs — beats quad when dh ≫ T (the in-chunk
        states are never formed, so their T·dh·N cost disappears from the
        T² term). Segment-end samples for ``collect_ends`` are rebuilt only
        at the (B, S) sampled rows.

    Both forms evaluate the identical operator (parity to f32 tolerance).
    The (B, L, H, dh, N) state trajectory is never materialized either way,
    and the chunk body is checkpointed so backward residuals stay at the
    raw inputs.

    ``intra="quad"`` is an exact pin of the default path (same
    ``_HEADS_CHUNK_CAP`` clamp, same trace); ``"dual"`` clamps at its own
    ``_HEADS_DUAL_CHUNK_CAP``. Within those bounds the caller's chunk
    stands.
    """
    if intra not in (None, "quad", "dual"):
        raise ValueError(f"unknown heads blocked intra mode {intra!r}")
    Bsz, L, H, P = u.shape
    N = B.shape[-1]
    T = min(chunk, L, _HEADS_DUAL_CHUNK_CAP if intra == "dual"
            else _HEADS_CHUNK_CAP)
    A32 = A.astype(cdt)
    reset = (positions == 0) if positions is not None else \
        jnp.zeros((Bsz, L), bool)
    pad = (-L) % T
    if pad:
        # Δ=0 ⇒ decay 1 / b-term 0 (state carried), no reset: identity steps
        u = jnp.pad(u, [(0, 0), (0, pad), (0, 0), (0, 0)])
        delta = jnp.pad(delta, [(0, 0), (0, pad), (0, 0)])
        B = jnp.pad(B, [(0, 0), (0, pad), (0, 0)])
        C = jnp.pad(C, [(0, 0), (0, pad), (0, 0)])
        reset = jnp.pad(reset, [(0, 0), (0, pad)])
    Lp = u.shape[1]
    nc = Lp // T
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), cdt)
    h0 = h0.astype(cdt)
    tril = jnp.tril(jnp.ones((T, T), bool))
    collect = collect_ends is not None
    nseg = collect_ends.shape[1] if collect else 0

    @jax.checkpoint
    def chunk_step(carry, xs):
        h_in, acc = carry
        uc, dc, Bc, Cc, rc, ci = xs  # (B,T,H,P), (B,T,H), (B,T,N)×2, (B,T)
        d32 = dc.astype(cdt)
        la = d32 * A32                                   # (B,T,H) log decay
        s = jnp.cumsum(la, axis=1)
        rid = jnp.cumsum(rc.astype(jnp.int32), axis=1)   # resets ≤ i
        m = (rid[:, :, None] == rid[:, None, :]) & tril[None]    # (B,T,T)
        mm = m[..., None]
        diff = s[:, :, None] - s[:, None, :]             # (B,T,T,H)
        dec = jnp.where(mm, jnp.exp(jnp.where(mm, diff, 0.0)), 0.0)
        bterm = (d32[..., None] * uc.astype(cdt))[..., None] * \
            Bc.astype(cdt)[:, :, None, None, :]          # (B,T,H,P,N)
        # the single-matmul step: (T,T)·(T, dh·N) batched only over (b, h)
        h = jnp.einsum("bijh,bjhpn->bihpn", dec, bterm)
        cin = jnp.where((rid == 0)[..., None], jnp.exp(s), 0.0)  # (B,T,H)
        h = h + cin[..., None, None] * h_in[:, None]
        if collect:
            # serving handoff: sample in-chunk states at segment ends
            local = collect_ends - ci * T                # (B, S)
            ok = (local >= 0) & (local < T)
            lcl = jnp.clip(local, 0, T - 1)[..., None, None, None]
            sel = jnp.take_along_axis(
                h, jnp.broadcast_to(lcl, (Bsz, nseg, H, P, N)), axis=1)
            acc = acc + jnp.where(ok[..., None, None, None], sel, 0)
        y = jnp.einsum("bihpn,bin->bihp", h, Cc.astype(cdt))
        return (h[:, -1], acc), y

    @jax.checkpoint
    def chunk_step_dual(carry, xs):
        h_in, acc = carry
        uc, dc, Bc, Cc, rc, ci = xs  # (B,T,H,P), (B,T,H), (B,T,N)×2, (B,T)
        d32 = dc.astype(cdt)
        la = d32 * A32                                   # (B,T,H) log decay
        s = jnp.cumsum(la, axis=1)
        rid = jnp.cumsum(rc.astype(jnp.int32), axis=1)   # resets ≤ i
        m = (rid[:, :, None] == rid[:, None, :]) & tril[None]    # (B,T,T)
        mm = m[..., None]
        diff = s[:, :, None] - s[:, None, :]             # (B,T,T,H)
        dec = jnp.where(mm, jnp.exp(jnp.where(mm, diff, 0.0)), 0.0)
        B32 = Bc.astype(cdt)
        C32 = Cc.astype(cdt)
        du = d32[..., None] * uc.astype(cdt)             # (B,T,H,P)  Δ·u
        # dual form: fold the (C_i · B_j) Gram matrix into the decay and
        # contract straight to outputs — the (B,T,H,dh,N) in-chunk states
        # are never formed
        G = dec * jnp.einsum("bin,bjn->bij", C32, B32)[..., None]  # (B,T,T,H)
        y = jnp.einsum("bijh,bjhp->bihp", G, du)
        cin = jnp.where((rid == 0)[..., None], jnp.exp(s), 0.0)    # (B,T,H)
        y = y + cin[..., None] * jnp.einsum("bhpn,bin->bihp", h_in, C32)
        # chunk-final carry state: one decay-weighted reduction per head
        h_out = jnp.einsum("bjh,bjhp,bjn->bhpn", dec[:, -1], du, B32) + \
            cin[:, -1][..., None, None] * h_in
        if collect:
            # rebuild states only at the sampled segment-end rows: gather
            # the (B, S) rows of dec/cin and redo the (S, T) contraction
            local = collect_ends - ci * T                # (B, S)
            ok = (local >= 0) & (local < T)
            lcl = jnp.clip(local, 0, T - 1)
            dec_e = jnp.take_along_axis(
                dec, jnp.broadcast_to(lcl[:, :, None, None],
                                      (Bsz, nseg, T, H)), axis=1)
            cin_e = jnp.take_along_axis(
                cin, jnp.broadcast_to(lcl[:, :, None], (Bsz, nseg, H)),
                axis=1)
            sel = jnp.einsum("bsjh,bjhp,bjn->bshpn", dec_e, du, B32) + \
                cin_e[..., None, None] * h_in[:, None]
            acc = acc + jnp.where(ok[..., None, None, None], sel, 0)
        return (h_out, acc), y

    xs = tuple(jnp.moveaxis(x.reshape((Bsz, nc, T) + x.shape[2:]), 1, 0)
               for x in (u, delta, B, C, reset))
    acc0 = jnp.zeros((Bsz, nseg, H, P, N), cdt) if collect else \
        jnp.zeros((), cdt)
    body = chunk_step_dual if intra == "dual" else chunk_step
    (h_last, h_ends), ys = jax.lax.scan(body, (h0, acc0),
                                        xs + (jnp.arange(nc),))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, Lp, H, P)[:, :L]
    if D is not None:
        y = y + (D.astype(cdt)[:, None] * u[:, :L].astype(cdt))
    y = y.astype(u.dtype)
    return y, h_last, (h_ends if collect else None)


def _seq_scan_heads(u, delta, A, B, C, D, positions, h0, cdt,
                    collect_ends=None):
    """Sequential per-head reference (y = C·h fused, scalar decay)."""
    Bsz, L, H, P = u.shape
    N = B.shape[-1]
    A32 = A.astype(cdt)
    reset = (positions == 0) if positions is not None else \
        jnp.zeros((Bsz, L), bool)
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), cdt)
    h0 = h0.astype(cdt)
    collect = collect_ends is not None
    nseg = collect_ends.shape[1] if collect else 0

    def step(carry, xs):
        h, acc = carry
        u_t, d_t, B_t, C_t, r_t, t = xs    # (B,H,P), (B,H), (B,N)×2, (B,)
        d32 = d_t.astype(cdt)
        a_t = jnp.exp(d32 * A32)                          # (B, H)
        a_t = jnp.where(r_t[:, None], 0.0, a_t)
        b_t = (d32[..., None] * u_t.astype(cdt))[..., None] * \
            B_t.astype(cdt)[:, None, None, :]             # (B, H, P, N)
        h = a_t[..., None, None] * h + b_t
        if collect:
            ok = (collect_ends == t)[..., None, None, None]
            acc = acc + jnp.where(ok, h[:, None], 0)
        y_t = jnp.einsum("bhpn,bn->bhp", h, C_t.astype(cdt))
        return (h, acc), y_t

    xs = (jnp.moveaxis(u, 1, 0), jnp.moveaxis(delta, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0),
          jnp.moveaxis(reset, 1, 0), jnp.arange(L))
    acc0 = jnp.zeros((Bsz, nseg, H, P, N), cdt) if collect else \
        jnp.zeros((), cdt)
    (h_last, h_ends), ys = jax.lax.scan(step, (h0, acc0), xs)
    y = jnp.moveaxis(ys, 0, 1)
    if D is not None:
        y = y + (D.astype(cdt)[:, None] * u.astype(cdt))
    y = y.astype(u.dtype)
    return y, h_last, (h_ends if collect else None)


def selective_scan_heads_step(h: jnp.ndarray, u_t: jnp.ndarray,
                              delta_t: jnp.ndarray, A: jnp.ndarray,
                              B_t: jnp.ndarray, C_t: jnp.ndarray,
                              D: Optional[jnp.ndarray] = None,
                              reset_t: Optional[jnp.ndarray] = None
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One head-structured decode step. h: (B, H, dh, N); u_t: (B, H, dh);
    delta_t: (B, H); A: (H,) scalar decay or (H, N) per-state (dh == 1);
    B_t, C_t: (B, N); D: (H,); reset_t: (B,) bool.

    Returns (y_t (B, H, dh), h_new (B, H, dh, N)).
    """
    cdt = h.dtype
    d32 = delta_t.astype(cdt)
    if A.ndim == 2:                       # Mamba-1 degenerate: (H, N), dh = 1
        a_t = jnp.exp(d32[..., None] * A.astype(cdt))[:, :, None, :]
    else:
        a_t = jnp.exp(d32 * A.astype(cdt))[..., None, None]   # (B,H,1,1)
    b_t = (d32[..., None] * u_t.astype(cdt))[..., None] * \
        B_t.astype(cdt)[:, None, None, :]                     # (B,H,dh,N)
    h_new = scan_step(h, jnp.broadcast_to(a_t, h.shape), b_t, reset_t)
    y_t = jnp.einsum("bhpn,bn->bhp", h_new, C_t.astype(cdt))
    if D is not None:
        y_t = y_t + D.astype(cdt)[:, None] * u_t.astype(cdt)
    return y_t.astype(u_t.dtype), h_new


def selective_scan_step(h: jnp.ndarray, u_t: jnp.ndarray, delta_t: jnp.ndarray,
                        A: jnp.ndarray, B_t: jnp.ndarray, C_t: jnp.ndarray,
                        D: Optional[jnp.ndarray] = None,
                        reset_t: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One Mamba-1 decode step — the dh = 1 case of
    ``selective_scan_heads_step``. h: (B, D, N); u_t, delta_t: (B, D);
    B_t, C_t: (B, N). Returns (y_t (B, D), h_new (B, D, N))."""
    y_t, h_new = selective_scan_heads_step(
        h[:, :, None, :], u_t[..., None], delta_t, A, B_t, C_t, D, reset_t)
    return y_t[..., 0], h_new[:, :, 0, :]
