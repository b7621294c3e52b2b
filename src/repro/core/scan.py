"""Generic *segmented* diagonal linear recurrence.

    h_t = a_t ⊙ h_{t-1} + b_t ,      y = all h_t            (inclusive scan)

with the PackMamba reset rule: wherever ``reset[t]`` is set (a packed-sequence
start, ``position_indices == 0``), force ``a_t → 0`` so no state crosses the
boundary. The paper's §3.4 correctness argument is algebraic — the combine
operator

    (a₁, b₁) ⊕ (a₂, b₂) = (a₂·a₁, a₂·b₁ + b₂)

is associative, and once some aₖ = 0 every composite multiplicative term that
spans k is 0, so no additive term from before k survives — hence it holds for
*any* schedule: the sequential scan, the Blelloch tree the paper modifies on
GPU, XLA's associative_scan, and our chunked TPU scan. ``test_pui.py`` checks
this property directly.

This one primitive backs: Mamba-1 selective scan (state (D, N)), RG-LRU
(state (D,)), and mLSTM (matrix state (H, dk, dv) with scalar per-head decay).

Schedule taxonomy (who wins when):
  * ``sequential``   — lax.scan over time. Reference & decode-step building
                       block. O(L) chain of tiny VPU ops; wins only at L
                       small enough that per-chunk setup overhead dominates.
  * ``associative``  — jax.lax.associative_scan over the full L (materializes
                       (B, L, *S) twice; fine for small state / short L).
  * ``chunked``      — lax.scan over L/T chunks carrying h, with an
                       intra-chunk associative scan. Peak memory O(B·T·S)
                       instead of O(B·L·S) for the scan internals; this is
                       the direct XLA analogue of the Pallas Mamba-1
                       kernel's grid-sequential VMEM-resident carry. Still
                       elementwise (VPU) work end to end.
  * ``blocked``      — SSD-style block-parallel schedule (Gu & Dao's
                       structured-state-space duality, adapted to segmented
                       scans): per chunk of length T, build the
                       lower-triangular cumulative-decay matrix
                       M[i,j] = Π_{j<k≤i} a_k (reset-masked: a→0 at segment
                       starts, so no product spans a boundary) and compute
                       all in-chunk states as one contraction h = M @ b,
                       plus an O(L/T) inter-chunk carry. Turns the O(L)
                       dependent elementwise chain into L/T matmul-shaped
                       contractions (MXU-friendly); costs O(T²·S) per-chunk
                       intermediates and ~T× the FLOPs, so it wins when the
                       hardware has idle matrix units and L ≫ T (see
                       benchmarks/run.py fig2). The selective-scan
                       specialization (exp-of-cumsum log decays, y = C·h
                       folded in, (B, L, D, N) never materialized) is
                       core/ssm.py::method='blocked'.
  * ``blocked`` with *per-head scalar decay* (Mamba-2 / SSD proper) — the
                       head-structured specialization: with state (H, dh, N)
                       and one scalar decay a_t per head (instead of one per
                       (d, n) element), the decay matrix M collapses from
                       (T, T, D, N) to a single (T, T) matrix per head, and
                       the whole in-chunk evaluation becomes ONE
                       (T, T)·(T, dh·N) matmul per head — the pure-MXU form
                       PackMamba's "bottleneck operator under diverse tensor
                       shapes" analysis calls for. Mamba-1 is the degenerate
                       case H = d_inner, dh = 1 with per-channel decay; both
                       variants dispatch through
                       core/ssm.py::selective_scan_heads. The TPU-kernel
                       twin is kernels/selective_scan.py::
                       schedule='blocked_heads'.

The Pallas kernels: the Mamba-1 kernel walks time with a per-step VPU
update (chunk carry in VMEM scratch), and ``schedule='blocked_heads'``
applies the per-head scalar-decay form as one dense (Tt, Tt) @ (Tt, dh·N)
matmul per subtile.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _bcast_reset(reset: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """Broadcast (B, L) reset mask to the rank of ``like`` ((B, L, *S))."""
    extra = like.ndim - reset.ndim
    return reset.reshape(reset.shape + (1,) * extra)


def apply_reset(a: jnp.ndarray, reset: Optional[jnp.ndarray]) -> jnp.ndarray:
    """PackMamba boundary rule: Ā→0 at sequence starts."""
    if reset is None:
        return a
    return jnp.where(_bcast_reset(reset, a), jnp.zeros_like(a), a)


def _combine(c1, c2):
    a1, b1 = c1
    a2, b2 = c2
    return a2 * a1, a2 * b1 + b2


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

def scan_sequential(a: jnp.ndarray, b: jnp.ndarray,
                    reset: Optional[jnp.ndarray] = None,
                    h0: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Time axis = 1. Returns (h_all (B,L,*S), h_last (B,*S))."""
    a = apply_reset(a, reset)
    if h0 is None:
        h0 = jnp.zeros(a.shape[:1] + a.shape[2:], a.dtype)

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    # scan over time: move axis 1 to front
    aT = jnp.moveaxis(a, 1, 0)
    bT = jnp.moveaxis(b, 1, 0)
    h_last, hs = jax.lax.scan(step, h0, (aT, bT))
    return jnp.moveaxis(hs, 0, 1), h_last


def scan_associative(a: jnp.ndarray, b: jnp.ndarray,
                     reset: Optional[jnp.ndarray] = None,
                     h0: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    a = apply_reset(a, reset)
    if h0 is not None:
        # fold h0 in as an extra b-term on step 0
        b = b.at[:, 0].add(a[:, 0] * h0)
    A, B = jax.lax.associative_scan(_combine, (a, b), axis=1)
    del A
    return B, B[:, -1]


def _chunk_scan(a, b, h0, chunk, chunk_body):
    """Shared scaffold for the chunk-carried schedules: pad L to a multiple
    of the chunk with identity steps (a=1, b=0 carry h unchanged), run
    ``chunk_body(h_in, (ac, bc)) -> (h_out, h_chunk)`` under lax.scan over
    the chunks, and stitch/slice the result back to (B, L, *S)."""
    Bsz, L = a.shape[0], a.shape[1]
    T = min(chunk, L)
    pad = (-L) % T
    if pad:
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2),
                    constant_values=1)
        b = jnp.pad(b, [(0, 0), (0, pad)] + [(0, 0)] * (b.ndim - 2))
    Lp = a.shape[1]
    nc = Lp // T
    S = a.shape[2:]
    if h0 is None:
        h0 = jnp.zeros((Bsz,) + S, a.dtype)
    aC = jnp.moveaxis(a.reshape((Bsz, nc, T) + S), 1, 0)   # (nc, B, T, *S)
    bC = jnp.moveaxis(b.reshape((Bsz, nc, T) + S), 1, 0)
    h_last, hs = jax.lax.scan(chunk_body, h0, (aC, bC))
    h_all = jnp.moveaxis(hs, 0, 1).reshape((Bsz, Lp) + S)[:, :L]
    return h_all, h_last


def scan_chunked(a: jnp.ndarray, b: jnp.ndarray,
                 reset: Optional[jnp.ndarray] = None,
                 h0: Optional[jnp.ndarray] = None,
                 chunk: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked scan: sequential across L/chunk, associative inside a chunk.

    Inside a chunk the pair scan yields, per position t (chunk-local),
    the composite (A_t, B_t) of steps [0..t]; then h_t = A_t·h_in + B_t.
    """
    a = apply_reset(a, reset)

    def step(h_in, ab):
        ac, bc = ab                      # (B, chunk, *S)
        A, Bc = jax.lax.associative_scan(_combine, (ac, bc), axis=1)
        h = A * h_in[:, None] + Bc       # (B, chunk, *S)
        return h[:, -1], h

    return _chunk_scan(a, b, h0, chunk, step)


def scan_blocked(a: jnp.ndarray, b: jnp.ndarray,
                 reset: Optional[jnp.ndarray] = None,
                 h0: Optional[jnp.ndarray] = None,
                 chunk: int = 32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Block-parallel (SSD-style) schedule. See module docstring.

    Per chunk of length T the in-chunk recurrence is evaluated closed-form:

        h_i = cp_i · h_in + Σ_{j≤i} M[i,j] · b_j
        M[i,j] = Π_{j<k≤i} a_k        cp_i = Π_{k≤i} a_k

    M is built with a cumprod along i of the broadcast decay (exact for any
    real a, no log-space needed), so the PackMamba reset (a→0) zeroes every
    boundary-spanning product automatically — including cp, which kills the
    inter-chunk carry past a reset. Peak intermediate is O(B·T²·*S) per
    chunk (the chunk body is rematerialized in the backward pass, so
    residuals stay O(B·L·*S)).
    """
    a = apply_reset(a, reset)

    @jax.checkpoint
    def chunk_step(h_in, ab):
        ac, bc = ab                                     # (B, T, *S)
        T = ac.shape[1]
        S = ac.shape[2:]
        ii = jnp.arange(T)[:, None]
        jj = jnp.arange(T)[None, :]
        strict = (ii > jj).reshape((1, T, T) + (1,) * len(S))
        lower = (ii >= jj).reshape((1, T, T) + (1,) * len(S))
        # Amat[b,i,j] = a_i for i > j else 1; cumprod over i gives M[i,j]
        amat = jnp.where(strict, ac[:, :, None],
                         jnp.ones_like(ac)[:, :1, None])
        M = jnp.where(lower, jnp.cumprod(amat, axis=1), 0)
        h = jnp.einsum("bij...,bj...->bi...", M, bc)
        cp = jnp.cumprod(ac, axis=1)                    # carry decay
        h = h + cp * h_in[:, None]
        return h[:, -1], h

    return _chunk_scan(a, b, h0, chunk, chunk_step)


_METHODS = {
    "sequential": scan_sequential,
    "associative": scan_associative,
    "chunked": scan_chunked,
    "blocked": scan_blocked,
}


def segmented_scan(a: jnp.ndarray, b: jnp.ndarray,
                   reset: Optional[jnp.ndarray] = None,
                   h0: Optional[jnp.ndarray] = None,
                   method: str = "chunked",
                   chunk: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatch. a, b: (B, L, *S); reset: (B, L) bool; h0: (B, *S).

    Returns (h_all (B, L, *S), h_last (B, *S)).
    """
    if a.shape != b.shape:
        raise ValueError(f"a/b shape mismatch {a.shape} vs {b.shape}")
    fn = _METHODS[method]
    if method in ("chunked", "blocked"):
        return fn(a, b, reset, h0, chunk=chunk)
    return fn(a, b, reset, h0)


def scan_step(h: jnp.ndarray, a_t: jnp.ndarray, b_t: jnp.ndarray,
              reset_t: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Single decode step of the recurrence (used by serve paths)."""
    if reset_t is not None:
        a_t = jnp.where(_bcast_reset(reset_t, a_t), jnp.zeros_like(a_t), a_t)
    return a_t * h + b_t


def gather_state_ends(h_traj: jnp.ndarray, ends: jnp.ndarray) -> jnp.ndarray:
    """Sample a (B, L, *S) state trajectory at per-segment end indices.

    Because segment resets stop state from crossing boundaries, the state at
    a segment's last token IS that segment's final state — this is the
    packed-prefill serving handoff. ``ends``: (B, S) int32, −1 = absent
    segment (→ zeros). Returns (B, S, *S)."""
    Bsz = h_traj.shape[0]
    S = ends.shape[1]
    tail = h_traj.shape[2:]
    idx = jnp.clip(ends, 0, h_traj.shape[1] - 1)
    idx = idx.reshape((Bsz, S) + (1,) * len(tail))
    g = jnp.take_along_axis(h_traj, jnp.broadcast_to(idx, (Bsz, S) + tail),
                            axis=1)
    ok = (ends >= 0).reshape((Bsz, S) + (1,) * len(tail))
    return jnp.where(ok, g, 0)
