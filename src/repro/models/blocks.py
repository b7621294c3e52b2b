"""Composable blocks for every architecture family.

Each block kind provides ``init_<kind>(key, cfg) -> params`` and
``apply_<kind>(params, x, ctx, cfg) -> x`` (full-sequence, packed-aware) and
``step_<kind>(params, x_t, cache, ctx, cfg) -> (x_t, cache)`` (single-token
decode). ``ctx`` carries the packing side-tensors (positions, segment_ids)
plus decode cursor.

Param leaves use conventional names (embed, head, wq, wkv, wo, w_gate, w_up,
w_down, experts_*, conv_w, A_log, …) that distributed/sharding.py
pattern-matches into PartitionSpecs.

State handoff (serving): every state-bearing ``apply_<kind>`` supports two
collect modes, selected by ``collect`` (= cache max_len) and
``collect_ends``:
  * per-ROW (``collect_ends=None``) — one right-padded sequence per row;
    state is frozen across the padding and the row's final state handed off
    (the historical ``model.prefill`` path).
  * per-SEGMENT (``collect_ends`` (B, S) int32, −1 = absent) — a PACKED row
    holds several prompts; the paper's reset rule makes the state at each
    segment's last token that segment's final state, so one packed forward
    hands off S caches per row (``model.prefill_packed``). State leaves gain
    a (B, S, …) leading pair.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.attention import (attention, decode_attention, rope, mrope)
from repro.core.recurrence import (rglru, rglru_step, mlstm, mlstm_step,
                                   slstm)
from repro.core import ssm as core_ssm
from repro.core.conv import conv1d_pack_update
from repro.kernels import ops as kops
from repro.configs.base import ArchConfig


@dataclasses.dataclass
class Ctx:
    """Per-call context threaded through blocks."""
    positions: Optional[jnp.ndarray] = None      # (B, L) intra-seq positions
    segment_ids: Optional[jnp.ndarray] = None    # (B, L)
    mrope_positions: Optional[jnp.ndarray] = None  # (B, L, S) for vlm
    # decode:
    cache_len: Optional[jnp.ndarray] = None      # (B,) current cursor
    reset_t: Optional[jnp.ndarray] = None        # (B,) new-sequence flag


def _norm(scale, x, eps):
    v = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(v + eps) *
            scale.astype(jnp.float32)).astype(x.dtype)


def _dense(key, din, dout, scale=None, dtype=jnp.float32):
    s = scale if scale is not None else din ** -0.5
    return jax.random.normal(key, (din, dout), dtype) * s


def _act(name: str):
    return jax.nn.gelu if name == "geglu" else jax.nn.silu


# ===========================================================================
# attention (+ shared MLP)
# ===========================================================================

def init_attn(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 5)
    return {
        "norm": jnp.ones((d,)),
        "wq": _dense(ks[0], d, H * hd),
        "wkv": _dense(ks[1], d, 2 * Hkv * hd),
        "wo": _dense(ks[2], H * hd, d, scale=(H * hd) ** -0.5),
    }


def _apply_rope(cfg, q, k, ctx: Ctx):
    if cfg.mrope_sections is not None:
        pos3 = ctx.mrope_positions
        if pos3 is None and ctx.positions is not None:
            pos3 = jnp.repeat(ctx.positions[..., None],
                              len(cfg.mrope_sections), axis=-1)
        if pos3 is not None:
            q = mrope(q, pos3, cfg.mrope_sections, cfg.rope_theta)
            k = mrope(k, pos3, cfg.mrope_sections, cfg.rope_theta)
        return q, k
    if ctx.positions is not None:
        q = rope(q, ctx.positions, cfg.rope_theta)
        k = rope(k, ctx.positions, cfg.rope_theta)
    return q, k


def apply_attn(p, x, ctx: Ctx, cfg: ArchConfig, collect: int = 0,
               collect_ends=None):
    B, L, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = _norm(p["norm"], x, cfg.norm_eps)
    q = (h @ p["wq"].astype(h.dtype)).reshape(B, L, H, hd)
    kv = (h @ p["wkv"].astype(h.dtype)).reshape(B, L, 2, Hkv, hd)
    k, v = kv[:, :, 0], kv[:, :, 1]
    q, k = _apply_rope(cfg, q, k, ctx)
    chunk = cfg.attn_chunk
    if chunk is None and L > 4096:
        chunk = 1024                       # online-softmax for long prefill
    o = attention(q, k, v,
                  segment_ids_q=ctx.segment_ids,
                  segment_ids_kv=ctx.segment_ids,
                  causal=not cfg.encoder_only,
                  window=cfg.attn_window,
                  chunk_kv=chunk)
    o = o.reshape(B, L, H * hd) @ p["wo"].astype(x.dtype)
    if collect:
        S = collect if cfg.attn_window is None else \
            min(collect, cfg.attn_window)
        if collect_ends is not None:
            lens = _ends_lens(ctx, collect_ends)
            return x + o, _ring_fill_ends(k, v, collect_ends, lens, S)
        lens = _valid(ctx, x).sum(-1)
        return x + o, _ring_fill(k, v, lens, S)
    return x + o


def init_attn_cache(cfg: ArchConfig, batch: int, max_len: int, dtype):
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    S = max_len if cfg.attn_window is None else min(max_len, cfg.attn_window)
    return {"k": jnp.zeros((batch, S, Hkv, hd), dtype),
            "v": jnp.zeros((batch, S, Hkv, hd), dtype)}


def _ring_fill(k, v, lens, S):
    """Lay a prefill's K/V into the ring-buffer layout step_attn uses:
    slot s holds the LAST token t < len with t ≡ s (mod S)."""
    B, L, Hkv, hd = k.shape
    s = jnp.arange(S)[None, :]                         # (1, S)
    nb = lens[:, None]                                 # (B, 1)
    t = s + ((nb - 1 - s) // S) * S                    # largest ≡ s (mod S)
    ok = (s < nb) & (t >= 0)
    tcl = jnp.clip(t, 0, L - 1)[..., None, None]       # (B, S, 1, 1)
    gk = jnp.take_along_axis(k, jnp.broadcast_to(tcl, (B, S) + k.shape[2:]),
                             axis=1)
    gv = jnp.take_along_axis(v, jnp.broadcast_to(tcl, (B, S) + v.shape[2:]),
                             axis=1)
    m = ok[..., None, None]
    return {"k": jnp.where(m, gk, 0), "v": jnp.where(m, gv, 0)}


def _ring_fill_ends(k, v, ends, lens, S):
    """Per-SEGMENT ring fill: slot s of segment (b, g) holds that segment's
    last token with intra-segment position ≡ s (mod S) — the packed-prefill
    generalization of ``_ring_fill``. Returns (B, Sg, S, Hkv, hd) K/V."""
    B, L, Hkv, hd = k.shape
    Sg = ends.shape[1]
    s = jnp.arange(S)[None, None, :]                   # (1, 1, S)
    nb = lens[..., None]                               # (B, Sg, 1)
    p = s + ((nb - 1 - s) // S) * S                    # largest ≡ s (mod S)
    ok = (s < nb) & (p >= 0) & (ends[..., None] >= 0)
    t = ends[..., None] - (nb - 1) + p                 # global token index
    tcl = jnp.clip(t, 0, L - 1).reshape(B, Sg * S)[..., None, None]
    gk = jnp.take_along_axis(
        k, jnp.broadcast_to(tcl, (B, Sg * S) + k.shape[2:]), axis=1)
    gv = jnp.take_along_axis(
        v, jnp.broadcast_to(tcl, (B, Sg * S) + v.shape[2:]), axis=1)
    m = ok[..., None, None]
    return {"k": jnp.where(m, gk.reshape(B, Sg, S, Hkv, hd), 0),
            "v": jnp.where(m, gv.reshape(B, Sg, S, Hkv, hd), 0)}


def _conv_tail(x_in, lens, W):
    """Last W-1 *valid* inputs per row → decode conv state (B, W-1, D)."""
    B, L, D = x_in.shape
    j = jnp.arange(W - 1)[None, :]                     # (1, W-1)
    t = lens[:, None] - (W - 1) + j                    # (B, W-1)
    ok = t >= 0
    tcl = jnp.clip(t, 0, L - 1)[..., None]
    g = jnp.take_along_axis(x_in, jnp.broadcast_to(tcl, (B, W - 1, D)),
                            axis=1)
    return jnp.where(ok[..., None], g, 0)


def _valid(ctx: Ctx, x):
    if ctx.segment_ids is None:
        return jnp.ones(x.shape[:2], bool)
    return ctx.segment_ids != 0


def _ends_lens(ctx: Ctx, ends):
    """Per-segment length at each end index: positions[end] + 1 (0 = absent).

    ends: (B, S) int32, −1 = absent. Returns (B, S) int32."""
    L = ctx.positions.shape[1]
    p = jnp.take_along_axis(ctx.positions, jnp.clip(ends, 0, L - 1), axis=1)
    return jnp.where(ends >= 0, p + 1, 0)


def _conv_tail_ends(x_in, ends, lens, W):
    """Last W-1 in-SEGMENT inputs per segment end → (B, S, W-1, D).

    Same layout as ``_conv_tail`` (zeros where the segment is shorter than
    W-1), one tail per packed segment instead of one per row."""
    B, L, D = x_in.shape
    S = ends.shape[1]
    j = jnp.arange(W - 1)[None, None, :]               # (1, 1, W-1)
    t = ends[..., None] - (W - 1) + 1 + j              # (B, S, W-1) global
    ok = (lens[..., None] - (W - 1) + j >= 0) & (ends[..., None] >= 0)
    tcl = jnp.clip(t, 0, L - 1).reshape(B, S * (W - 1))[..., None]
    g = jnp.take_along_axis(
        x_in, jnp.broadcast_to(tcl, (B, S * (W - 1), D)), axis=1)
    return jnp.where(ok[..., None], g.reshape(B, S, W - 1, D), 0)


def step_attn(p, x_t, cache, ctx: Ctx, cfg: ArchConfig):
    """x_t: (B, 1, d). Writes K/V at ctx.cache_len then attends."""
    B, _, d = x_t.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = _norm(p["norm"], x_t, cfg.norm_eps)
    q = (h @ p["wq"].astype(h.dtype)).reshape(B, 1, H, hd)
    kv = (h @ p["wkv"].astype(h.dtype)).reshape(B, 1, 2, Hkv, hd)
    k, v = kv[:, :, 0], kv[:, :, 1]
    pos = ctx.cache_len[:, None]                      # (B, 1) intra-seq pos
    sctx = Ctx(positions=pos, mrope_positions=(
        jnp.repeat(pos[..., None], len(cfg.mrope_sections), axis=-1)
        if cfg.mrope_sections is not None else None))
    q, k = _apply_rope(cfg, q, k, sctx)
    # ring-buffer write for windowed attention (cache size = window keeps
    # long_500k decode state bounded), linear write otherwise
    S = cache["k"].shape[1]
    slot = ctx.cache_len % S
    bidx = jnp.arange(B)
    kc = cache["k"].at[bidx, slot].set(k[:, 0])
    vc = cache["v"].at[bidx, slot].set(v[:, 0])
    if cfg.attn_window is not None:
        o = _ring_decode(q[:, 0], kc, vc, ctx.cache_len, cfg.attn_window)
    else:
        o = decode_attention(q[:, 0], kc, vc, ctx.cache_len, window=None)
    o = o.reshape(B, 1, H * hd) @ p["wo"].astype(x_t.dtype)
    return x_t + o, {"k": kc, "v": vc}


def _ring_decode(q_t, kc, vc, cur, window):
    """Decode attention over a ring buffer of size S ≥ window."""
    B, S, Hkv, hd = kc.shape
    idx = jnp.arange(S)[None, :]
    slot_age = (cur[:, None] % S - idx) % S          # age of each slot
    valid = (slot_age < window) & (slot_age <= cur[:, None])
    H = q_t.shape[1]
    G = H // Hkv
    s = jnp.einsum("bhgd,bkhd->bhgk", q_t.reshape(B, Hkv, G, hd), kc,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(vc.dtype), vc)
    return o.reshape(B, H, hd)


def init_mlp(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {"norm": jnp.ones((d,)),
            "w_gate": _dense(ks[0], d, ff),
            "w_up": _dense(ks[1], d, ff),
            "w_down": _dense(ks[2], ff, d, scale=ff ** -0.5)}


def apply_mlp(p, x, ctx: Ctx, cfg: ArchConfig):
    h = _norm(p["norm"], x, cfg.norm_eps)
    g = _act(cfg.act)(h @ p["w_gate"].astype(h.dtype))
    u = h @ p["w_up"].astype(h.dtype)
    return x + (g * u) @ p["w_down"].astype(x.dtype)


# ===========================================================================
# MoE FFN (sort-based dispatch, EP-shardable)
# ===========================================================================

def init_moe(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 7)
    p = {"norm": jnp.ones((d,)),
         "router": _dense(ks[0], d, E),
         "experts_gate": jax.random.normal(ks[1], (E, d, ff)) * d ** -0.5,
         "experts_up": jax.random.normal(ks[2], (E, d, ff)) * d ** -0.5,
         "experts_down": jax.random.normal(ks[3], (E, ff, d)) * ff ** -0.5}
    if cfg.n_shared_experts:
        sff = ff * cfg.n_shared_experts
        p["shared_gate"] = _dense(ks[4], d, sff)
        p["shared_up"] = _dense(ks[5], d, sff)
        p["shared_down"] = _dense(ks[6], sff, d, scale=sff ** -0.5)
    return p


def _moe_ffn(p, x, cfg: ArchConfig):
    """x: (T, d) → (T, d), plus aux losses. Sort-based capacity dispatch:
    O(T·K) memory, experts batched on the leading (EP-shardable) axis."""
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)                 # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    # capacity per expert (static)
    C = int(math.ceil(T * K / E * cfg.capacity_factor))
    C = max(8, -(-C // 8) * 8)
    flat_e = expert_idx.reshape(-1)                                 # (T·K,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # rank within expert group
    tk = T * K
    counts = jnp.bincount(sorted_e, length=E)
    seg_start = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                 jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(tk) - seg_start[sorted_e]
    keep = rank < C
    token_of = order // K                                           # (T·K,)
    # dispatch into (E, C, d)
    slot = jnp.where(keep, sorted_e * C + rank, E * C)              # drop → OOB
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(x[token_of])
    xe = buf[:E * C].reshape(E, C, d)
    # expert FFN, batched over E
    g = _act(cfg.act)(jnp.einsum("ecd,edf->ecf", xe,
                                 p["experts_gate"].astype(x.dtype)))
    u = jnp.einsum("ecd,edf->ecf", xe, p["experts_up"].astype(x.dtype))
    ye = jnp.einsum("ecf,efd->ecd", g * u,
                    p["experts_down"].astype(x.dtype))
    # combine back: gather each (t, k) choice's output
    ye_flat = jnp.concatenate([ye.reshape(E * C, d),
                               jnp.zeros((1, d), x.dtype)], axis=0)
    gathered = ye_flat[slot]                                        # (T·K, d)
    contrib = jnp.zeros((T, d), x.dtype).at[token_of].add(
        gathered * gate_vals.reshape(-1)[order][:, None].astype(x.dtype))
    # aux: load-balance + router z-loss
    me = probs.mean(0)                                              # (E,)
    ce = jnp.zeros(E, jnp.float32).at[flat_e].add(1.0) / (T * K)
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    if "shared_gate" in p:
        g = _act(cfg.act)(x @ p["shared_gate"].astype(x.dtype))
        u = x @ p["shared_up"].astype(x.dtype)
        contrib = contrib + (g * u) @ p["shared_down"].astype(x.dtype)
    return contrib, {"lb_loss": lb_loss, "z_loss": z_loss}


def apply_moe(p, x, ctx: Ctx, cfg: ArchConfig):
    B, L, d = x.shape
    h = _norm(p["norm"], x, cfg.norm_eps).reshape(B * L, d)
    Tc = cfg.moe_token_chunk
    if Tc and B * L > Tc and (B * L) % Tc == 0:
        # bound dispatch-buffer memory: route/dispatch/combine per token
        # chunk (capacity applies per chunk — slightly better balanced)
        ys, auxs = jax.lax.map(lambda hh: _moe_ffn(p, hh, cfg),
                               h.reshape(-1, Tc, d))
        y = ys.reshape(B * L, d)
        aux = jax.tree.map(lambda a: a.mean(), auxs)
    else:
        y, aux = _moe_ffn(p, h, cfg)
    return x + y.reshape(B, L, d), aux


# ===========================================================================
# Mamba block (the paper's architecture)
# ===========================================================================

def init_mamba(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, di, N, W, dtr = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.d_conv, \
        cfg.dtr
    ks = jax.random.split(key, 6)
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "norm": jnp.ones((d,)),
        "in_proj": _dense(ks[0], d, 2 * di),
        "conv_w": jax.random.normal(ks[1], (W, di)) * W ** -0.5,
        "conv_b": jnp.zeros((di,)),
        "x_proj": _dense(ks[2], di, dtr + 2 * N),
        "dt_w": _dense(ks[3], dtr, di, scale=dtr ** -0.5),
        "dt_b": jnp.full((di,), -4.6),        # softplus⁻¹(0.01)
        "A_log": jnp.log(A),
        "D": jnp.ones((di,)),
        "out_proj": _dense(ks[4], di, d, scale=di ** -0.5),
    }


def _scan_backend(cfg: ArchConfig, A) -> str:
    """Backend of a block's sequence scan, 'pallas' or 'xla': the one place
    that chooses it. Read while tracing, so a CPU trace holds only the XLA
    evaluator.

    | decay ``A``            | use_pallas | scan_dtype | platform | backend |
    | ---------------------- | ---------- | ---------- | -------- | ------- |
    | (D, N), Mamba-1        | None       | float32    | TPU      | pallas  |
    | (D, N), Mamba-1        | None       | float32    | other    | xla     |
    | (D, N), Mamba-1        | None       | other      | any      | xla     |
    | (H,), Mamba-2 heads    | None       | any        | any      | xla     |
    | any                    | True       | any        | any      | pallas  |
    | any                    | False      | any        | any      | xla     |

    Unset, the kernel runs only where it is compiled (a TPU) and at its own
    precision (float32 state); scalar-decay heads keep the XLA evaluator,
    whose blocked form is matmul-shaped. True runs the kernels everywhere
    (Mosaic on a TPU, interpreted elsewhere). The state-collecting prefills
    (``collect`` in apply_mamba/apply_mamba2, chunk_mamba/chunk_mamba2)
    call core/ssm.py directly, so they run the XLA evaluator whatever this
    says; its in-chunk form is core/ssm.py's choice.
    """
    if cfg.use_pallas is not None:
        return "pallas" if cfg.use_pallas else "xla"
    return "pallas" if (A.ndim == 2 and cfg.scan_dtype == "float32" and
                        jax.default_backend() == "tpu") else "xla"


def _mamba_in(p, x, cfg: ArchConfig):
    """The block's input: its norm, then ``in_proj`` split into the scan
    branch and the gate."""
    with jax.named_scope("mamba.norm"):
        h = _norm(p["norm"], x, cfg.norm_eps)
    with jax.named_scope("mamba.in_proj"):
        xz = h @ p["in_proj"].astype(h.dtype)
        x_in, z = jnp.split(xz, 2, axis=-1)
    return x_in, z


def _mamba_gates(p, x_c, dtype, cfg: ArchConfig):
    """``x_proj``, the dt projection and its softplus: (delta, B, C)."""
    N, dtr = cfg.d_state, cfg.dtr
    with jax.named_scope("mamba.x_proj"):
        dbl = x_c @ p["x_proj"].astype(dtype)
        dt_low, Bm, Cm = jnp.split(dbl, [dtr, dtr + N], axis=-1)
        delta = jax.nn.softplus(dt_low @ p["dt_w"].astype(dtype) +
                                p["dt_b"].astype(dtype))
    return delta, Bm, Cm


def _mamba_out(p, x, y, z):
    """The gate, ``out_proj`` and the residual."""
    with jax.named_scope("mamba.out_proj"):
        y = y * jax.nn.silu(z)
        return x + y @ p["out_proj"].astype(x.dtype)


def apply_mamba(p, x, ctx: Ctx, cfg: ArchConfig, collect: int = 0,
                collect_ends=None):
    """Each piece runs under a named scope (``mamba.norm``, ``.in_proj``,
    ``.conv``, ``.x_proj``, ``.scan``, ``.out_proj``), so the ops of a
    device trace say which piece they belong to."""
    backend = "pallas" if cfg.use_pallas else "xla"
    dt = x.dtype
    x_in, z = _mamba_in(p, x, cfg)
    with jax.named_scope("mamba.conv"):
        x_c = kops.conv1d_pack(x_in, p["conv_w"].astype(dt),
                               p["conv_b"].astype(dt),
                               ctx.positions, backend=backend)
        x_c = jax.nn.silu(x_c)
    delta, Bm, Cm = _mamba_gates(p, x_c, dt, cfg)
    if collect and collect_ends is not None:
        # per-SEGMENT handoff: resets already isolate segments, so the state
        # sampled at each segment end IS its final state — no freezing, and
        # padding (pos == 0 ⇒ reset) cannot leak into earlier samples.
        with jax.named_scope("mamba.scan"):
            A = -jnp.exp(p["A_log"])
            y, h_ends = core_ssm.selective_scan(
                x_c, delta, A, Bm, Cm, p["D"], positions=ctx.positions,
                method=cfg.scan_impl, chunk=cfg.scan_chunk,
                intra=cfg.scan_intra, collect_ends=collect_ends)
        with jax.named_scope("mamba.conv"):
            conv = _conv_tail_ends(x_in, collect_ends,
                                   _ends_lens(ctx, collect_ends), cfg.d_conv)
        return _mamba_out(p, x, y, z), {"conv": conv, "ssm": h_ends}
    if collect:
        # freeze state across right-padding: Δ=0 ⇒ Ā=1, B̄x=0. Padding
        # positions are 0, which would trigger the Ā→0 reset and zero the
        # handed-off state — neutralize them (pos→1) there.
        with jax.named_scope("mamba.scan"):
            A = -jnp.exp(p["A_log"])
            valid = _valid(ctx, x)
            delta = delta * valid[..., None].astype(delta.dtype)
            pos_nz = jnp.where(valid, ctx.positions, 1)
            y, h_last = core_ssm.selective_scan(
                x_c, delta, A, Bm, Cm, p["D"], positions=pos_nz,
                method=cfg.scan_impl, chunk=cfg.scan_chunk,
                return_state=True, intra=cfg.scan_intra)
        with jax.named_scope("mamba.conv"):
            conv = _conv_tail(x_in, valid.sum(-1), cfg.d_conv)
        return _mamba_out(p, x, y, z), {"conv": conv, "ssm": h_last}
    with jax.named_scope("mamba.scan"):
        A = -jnp.exp(p["A_log"])
        y = kops.selective_scan(x_c, delta, A, Bm, Cm, p["D"],
                                positions=ctx.positions,
                                backend=_scan_backend(cfg, A),
                                xla_chunk=cfg.scan_chunk,
                                xla_method=cfg.scan_impl,
                                xla_dtype=(None
                                           if cfg.scan_dtype == "float32"
                                           else cfg.scan_dtype),
                                xla_intra=cfg.scan_intra)
    return _mamba_out(p, x, y, z)


def init_mamba_cache(cfg: ArchConfig, batch: int, dtype):
    di, N, W = cfg.d_inner, cfg.d_state, cfg.d_conv
    return {"conv": jnp.zeros((batch, W - 1, di), dtype),
            "ssm": jnp.zeros((batch, di, N), jnp.float32)}


def step_mamba(p, x_t, cache, ctx: Ctx, cfg: ArchConfig):
    B = x_t.shape[0]
    di, N, dtr = cfg.d_inner, cfg.d_state, cfg.dtr
    h = _norm(p["norm"], x_t, cfg.norm_eps)
    xz = (h[:, 0] @ p["in_proj"].astype(h.dtype))
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_c, conv_state = conv1d_pack_update(
        x_in, cache["conv"], p["conv_w"].astype(h.dtype),
        p["conv_b"].astype(h.dtype), ctx.reset_t)
    x_c = jax.nn.silu(x_c)
    dbl = x_c @ p["x_proj"].astype(h.dtype)
    dt_low, Bm, Cm = jnp.split(dbl, [dtr, dtr + N], axis=-1)
    delta = jax.nn.softplus(dt_low @ p["dt_w"].astype(h.dtype) +
                            p["dt_b"].astype(h.dtype))
    A = -jnp.exp(p["A_log"])
    y, ssm = core_ssm.selective_scan_step(
        cache["ssm"], x_c, delta, A, Bm, Cm, p["D"], reset_t=ctx.reset_t)
    y = y * jax.nn.silu(z)
    out = y @ p["out_proj"].astype(x_t.dtype)
    return x_t + out[:, None], {"conv": conv_state, "ssm": ssm}


# ===========================================================================
# Mamba-2 block (SSD: scalar per-head decay, head-structured state)
# ===========================================================================

def init_mamba2(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, di, N, W = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.d_conv
    H = cfg.n_ssm_heads
    ks = jax.random.split(key, 6)
    # Mamba-2 init: A ~ U[1, 16] per head; A = -exp(A_log) < 0
    A = jax.random.uniform(ks[5], (H,), minval=1.0, maxval=16.0)
    out = {
        "norm": jnp.ones((d,)),
        "in_proj": _dense(ks[0], d, 2 * di),
        "conv_w": jax.random.normal(ks[1], (W, di)) * W ** -0.5,
        "conv_b": jnp.zeros((di,)),
        # grouped B/C projections: one (B, C) pair shared by every head
        "bc_proj": _dense(ks[2], di, 2 * N),
        # per-head Δ head (no low-rank bottleneck: H ≪ d_inner already)
        "dt_proj": _dense(ks[3], di, H),
        "dt_b": jnp.full((H,), -4.6),         # softplus⁻¹(0.01)
        "A_log": jnp.log(A),
        "D": jnp.ones((H,)),
        "out_proj": _dense(ks[4], di, d, scale=di ** -0.5),
    }
    if cfg.ssm_norm == "rms_gate":
        out["ssm_norm_w"] = jnp.ones((di,))
    return out


def _mamba2_gates(p, x_c, cfg: ArchConfig):
    """Shared projection head: x_c (..., di) → (Δ (..., H), B, C (..., N))."""
    bc = x_c @ p["bc_proj"].astype(x_c.dtype)
    Bm, Cm = jnp.split(bc, 2, axis=-1)
    delta = jax.nn.softplus(x_c @ p["dt_proj"].astype(x_c.dtype) +
                            p["dt_b"].astype(x_c.dtype))
    return delta, Bm, Cm


def _mamba2_gate_out(p, y, z, cfg: ArchConfig):
    """Mamba-2 output gate: y·silu(z), with the optional RMSNorm-before-
    out_proj variant (``ssm_norm="rms_gate"``: normalize the gated product
    and rescale by a learned (d_inner,) weight — Mamba-2's `rmsnorm` knob,
    which decouples out_proj's input scale from sequence statistics)."""
    g = y * jax.nn.silu(z)
    if "ssm_norm_w" in p:
        g = _norm(p["ssm_norm_w"], g, cfg.norm_eps)
    return g


def apply_mamba2(p, x, ctx: Ctx, cfg: ArchConfig, collect: int = 0,
                 collect_ends=None):
    B, L, d = x.shape
    di, H, P = cfg.d_inner, cfg.n_ssm_heads, cfg.ssm_hd
    backend = "pallas" if cfg.use_pallas else "xla"
    h = _norm(p["norm"], x, cfg.norm_eps)
    xz = h @ p["in_proj"].astype(h.dtype)
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_c = kops.conv1d_pack(x_in, p["conv_w"].astype(h.dtype),
                           p["conv_b"].astype(h.dtype),
                           ctx.positions, backend=backend)
    x_c = jax.nn.silu(x_c)
    delta, Bm, Cm = _mamba2_gates(p, x_c, cfg)
    A = -jnp.exp(p["A_log"])
    u_h = x_c.reshape(B, L, H, P)
    if collect and collect_ends is not None:
        # per-SEGMENT handoff — same protocol as apply_mamba: no freezing,
        # sample the head-structured state at each segment end.
        y, h_ends = core_ssm.selective_scan_heads(
            u_h, delta, A, Bm, Cm, p["D"], positions=ctx.positions,
            method="blocked", chunk=cfg.scan_chunk, intra=cfg.scan_intra,
            collect_ends=collect_ends)
        state = {"conv": _conv_tail_ends(x_in, collect_ends,
                                         _ends_lens(ctx, collect_ends),
                                         cfg.d_conv),
                 "ssm": h_ends}
        y = _mamba2_gate_out(p, y.reshape(B, L, di), z, cfg)
        return x + y @ p["out_proj"].astype(x.dtype), state
    if collect:
        # freeze state across right-padding (Δ=0 ⇒ decay 1, b-term 0) and
        # neutralize the pos==0 reset at padding slots — same protocol as
        # apply_mamba.
        valid = _valid(ctx, x)
        delta = delta * valid[..., None].astype(delta.dtype)
        pos_nz = jnp.where(valid, ctx.positions, 1)
        y, h_last = core_ssm.selective_scan_heads(
            u_h, delta, A, Bm, Cm, p["D"], positions=pos_nz,
            method="blocked", chunk=cfg.scan_chunk, return_state=True,
            intra=cfg.scan_intra)
        state = {"conv": _conv_tail(x_in, valid.sum(-1), cfg.d_conv),
                 "ssm": h_last}
        y = _mamba2_gate_out(p, y.reshape(B, L, di), z, cfg)
        return x + y @ p["out_proj"].astype(x.dtype), state
    y = kops.selective_scan_heads(u_h, delta, A, Bm, Cm, p["D"],
                                  positions=ctx.positions,
                                  backend=_scan_backend(cfg, A),
                                  xla_chunk=cfg.scan_chunk,
                                  xla_dtype=(None
                                             if cfg.scan_dtype == "float32"
                                             else cfg.scan_dtype),
                                  xla_intra=cfg.scan_intra)
    y = _mamba2_gate_out(p, y.reshape(B, L, di), z, cfg)
    return x + y @ p["out_proj"].astype(x.dtype)


def init_mamba2_cache(cfg: ArchConfig, batch: int, dtype):
    di, N, W = cfg.d_inner, cfg.d_state, cfg.d_conv
    H, P = cfg.n_ssm_heads, cfg.ssm_hd
    return {"conv": jnp.zeros((batch, W - 1, di), dtype),
            "ssm": jnp.zeros((batch, H, P, N), jnp.float32)}


def step_mamba2(p, x_t, cache, ctx: Ctx, cfg: ArchConfig):
    B = x_t.shape[0]
    di, H, P = cfg.d_inner, cfg.n_ssm_heads, cfg.ssm_hd
    h = _norm(p["norm"], x_t, cfg.norm_eps)
    xz = (h[:, 0] @ p["in_proj"].astype(h.dtype))
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_c, conv_state = conv1d_pack_update(
        x_in, cache["conv"], p["conv_w"].astype(h.dtype),
        p["conv_b"].astype(h.dtype), ctx.reset_t)
    x_c = jax.nn.silu(x_c)
    delta, Bm, Cm = _mamba2_gates(p, x_c, cfg)
    A = -jnp.exp(p["A_log"])
    y, ssm = core_ssm.selective_scan_heads_step(
        cache["ssm"], x_c.reshape(B, H, P), delta, A, Bm, Cm, p["D"],
        reset_t=ctx.reset_t)
    y = _mamba2_gate_out(p, y.reshape(B, di), z, cfg)
    out = y @ p["out_proj"].astype(x_t.dtype)
    return x_t + out[:, None], {"conv": conv_state, "ssm": ssm}


# ===========================================================================
# RG-LRU recurrent block (RecurrentGemma / Griffin)
# ===========================================================================

RGLRU_C_ = 8.0


def init_rec(key, cfg: ArchConfig) -> Dict[str, Any]:
    d = cfg.d_model
    lw = cfg.lru_width or d
    W = cfg.conv_width
    nb = cfg.lru_gate_blocks
    if lw % nb:
        raise ValueError(f"lru_width {lw} % gate blocks {nb} != 0")
    c = lw // nb
    ks = jax.random.split(key, 7)
    # a_param init so that a = exp(-c·softplus(Λ)) lands in [0.9, 0.999]
    u = jax.random.uniform(ks[5], (lw,), minval=0.9, maxval=0.999)
    a_param = jnp.log(jnp.expm1(-jnp.log(u) / RGLRU_C_))  # softplus⁻¹(-ln u/c)
    return {
        "norm": jnp.ones((d,)),
        "w_x": _dense(ks[0], d, lw),
        "w_y": _dense(ks[1], d, lw),
        "conv_w": jax.random.normal(ks[2], (W, lw)) * W ** -0.5,
        "conv_b": jnp.zeros((lw,)),
        # Griffin-faithful block-diagonal gate projections: (nb, c, c) blocks
        # are local to a model-axis shard — the gates never cross shards.
        "w_r": jax.random.normal(ks[3], (nb, c, c)) * c ** -0.5,
        "w_i": jax.random.normal(ks[4], (nb, c, c)) * c ** -0.5,
        "a_param": a_param,
        "wo": _dense(ks[6], lw, d, scale=lw ** -0.5),
    }


def _gate_blockdiag(x_c, w, nb):
    """x_c: (B, L, lw) → block-diagonal projection with (nb, c, c)."""
    B, L, lw = x_c.shape
    xb = x_c.reshape(B, L, nb, lw // nb)
    return jnp.einsum("blnc,ncd->blnd", xb, w).reshape(B, L, lw)


def apply_rec(p, x, ctx: Ctx, cfg: ArchConfig, collect: int = 0,
              collect_ends=None):
    backend = "pallas" if cfg.use_pallas else "xla"
    nb = cfg.lru_gate_blocks
    h = _norm(p["norm"], x, cfg.norm_eps)
    y_branch = jax.nn.gelu(h @ p["w_y"].astype(h.dtype))
    x_branch = h @ p["w_x"].astype(h.dtype)
    x_c = kops.conv1d_pack(x_branch, p["conv_w"].astype(h.dtype),
                           p["conv_b"].astype(h.dtype), ctx.positions,
                           backend=backend)
    r = jax.nn.sigmoid(_gate_blockdiag(x_c, p["w_r"].astype(h.dtype), nb))
    i = jax.nn.sigmoid(_gate_blockdiag(x_c, p["w_i"].astype(h.dtype), nb))
    cdt = None if cfg.scan_dtype == "float32" else cfg.scan_dtype
    if collect and collect_ends is not None:
        # per-SEGMENT handoff: the RG-LRU state trajectory is its output, so
        # segment-end states are a free gather inside rglru — no freezing.
        lru, _, h_ends = rglru(x_c, r, i, p["a_param"], ctx.positions,
                               method="chunked", chunk=cfg.scan_chunk,
                               compute_dtype=cdt, collect_ends=collect_ends)
        out = (lru * y_branch) @ p["wo"].astype(x.dtype)
        return x + out, {"conv": _conv_tail_ends(
            x_branch, collect_ends, _ends_lens(ctx, collect_ends),
            cfg.conv_width), "h": h_ends}
    pos_rec = ctx.positions
    if collect:
        # freeze across padding: r=0 ⇒ a=1, and then b = √(1-a²)·i·x = 0;
        # also neutralize the pos==0 reset at padding slots
        vmask = _valid(ctx, x)
        valid = vmask[..., None].astype(r.dtype)
        r, i = r * valid, i * valid
        pos_rec = jnp.where(vmask, ctx.positions, 1)
    lru, h_last = rglru(x_c, r, i, p["a_param"], pos_rec,
                        method="chunked", chunk=cfg.scan_chunk,
                        compute_dtype=cdt)
    out = (lru * y_branch) @ p["wo"].astype(x.dtype)
    if collect:
        lens = _valid(ctx, x).sum(-1)
        return x + out, {"conv": _conv_tail(x_branch, lens, cfg.conv_width),
                         "h": h_last}
    return x + out


def init_rec_cache(cfg: ArchConfig, batch: int, dtype):
    lw = cfg.lru_width or cfg.d_model
    return {"conv": jnp.zeros((batch, cfg.conv_width - 1, lw), dtype),
            "h": jnp.zeros((batch, lw), jnp.float32)}


def step_rec(p, x_t, cache, ctx: Ctx, cfg: ArchConfig):
    nb = cfg.lru_gate_blocks
    h = _norm(p["norm"], x_t, cfg.norm_eps)
    y_branch = jax.nn.gelu(h[:, 0] @ p["w_y"].astype(h.dtype))
    x_branch = h[:, 0] @ p["w_x"].astype(h.dtype)
    x_c, conv_state = conv1d_pack_update(
        x_branch, cache["conv"], p["conv_w"].astype(h.dtype),
        p["conv_b"].astype(h.dtype), ctx.reset_t)
    r = jax.nn.sigmoid(_gate_blockdiag(x_c[:, None],
                                       p["w_r"].astype(h.dtype), nb)[:, 0])
    i = jax.nn.sigmoid(_gate_blockdiag(x_c[:, None],
                                       p["w_i"].astype(h.dtype), nb)[:, 0])
    y, hn = rglru_step(cache["h"], x_c, r, i, p["a_param"], ctx.reset_t)
    out = (y * y_branch) @ p["wo"].astype(x_t.dtype)
    return x_t + out[:, None], {"conv": conv_state, "h": hn}


# ===========================================================================
# xLSTM blocks
# ===========================================================================

def init_mlstm(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, H = cfg.d_model, cfg.n_heads
    pf = int(cfg.proj_factor * d)
    W = cfg.conv_width
    ks = jax.random.split(key, 8)
    return {
        "norm": jnp.ones((d,)),
        "w_upx": _dense(ks[0], d, pf),
        "w_upz": _dense(ks[1], d, pf),
        "conv_w": jax.random.normal(ks[2], (W, pf)) * W ** -0.5,
        "conv_b": jnp.zeros((pf,)),
        "wq": _dense(ks[3], pf, pf),
        "wk": _dense(ks[4], pf, pf),
        "wv": _dense(ks[5], pf, pf),
        "w_if": _dense(ks[6], pf, 2 * H),
        "b_if": jnp.concatenate([jnp.zeros(H), jnp.full((H,), 3.0)]),
        "w_down": _dense(ks[7], pf, d, scale=pf ** -0.5),
    }


def apply_mlstm(p, x, ctx: Ctx, cfg: ArchConfig, collect: int = 0,
                collect_ends=None):
    B, L, d = x.shape
    H = cfg.n_heads
    pf = p["w_upx"].shape[1]
    dh = pf // H
    backend = "pallas" if cfg.use_pallas else "xla"
    hin = _norm(p["norm"], x, cfg.norm_eps)
    x_in = hin @ p["w_upx"].astype(hin.dtype)
    z = hin @ p["w_upz"].astype(hin.dtype)
    x_c = kops.conv1d_pack(x_in, p["conv_w"].astype(hin.dtype),
                           p["conv_b"].astype(hin.dtype), ctx.positions,
                           backend=backend)
    x_c = jax.nn.silu(x_c)
    q = (x_c @ p["wq"].astype(hin.dtype)).reshape(B, L, H, dh)
    k = (x_c @ p["wk"].astype(hin.dtype)).reshape(B, L, H, dh)
    v = (x_in @ p["wv"].astype(hin.dtype)).reshape(B, L, H, dh)
    g = x_c @ p["w_if"].astype(hin.dtype) + p["b_if"].astype(hin.dtype)
    logi, f_pre = jnp.split(g, 2, axis=-1)
    logf = jax.nn.log_sigmoid(f_pre.astype(jnp.float32))
    logi = logi.astype(jnp.float32)
    if collect and collect_ends is not None:
        # per-SEGMENT handoff. The mLSTM matrix-state trajectory is never
        # materialized (chunkwise form), so per-segment finals are computed
        # by vmapping the freeze trick over segments: gates outside segment
        # g are identity (f'=1, i'=0), so the row's FINAL state equals the
        # state at g's last token. The big projections above run once; only
        # the O(L·H·dk·dv) state update repeats S times.
        y = mlstm(q, k, v, logf, logi, positions=ctx.positions,
                  chunk=cfg.scan_chunk)

        def one_seg(sid):
            msk = ctx.segment_ids == sid
            lf = jnp.where(msk[..., None], logf, 0.0)
            li = jnp.where(msk[..., None], logi, -1e30)
            ps = jnp.where(msk, ctx.positions, 1)
            _, st = mlstm(q, k, v, lf, li, positions=ps,
                          chunk=cfg.scan_chunk, return_state=True)
            return st

        nseg = collect_ends.shape[1]
        Cs, ns, ms = jax.vmap(one_seg, out_axes=1)(
            jnp.arange(1, nseg + 1, dtype=jnp.int32))
        state = {"conv": _conv_tail_ends(x_in, collect_ends,
                                         _ends_lens(ctx, collect_ends),
                                         cfg.conv_width),
                 "C": Cs, "n": ns, "m": ms}
        y = y.reshape(B, L, pf) * jax.nn.silu(z)
        return x + y @ p["w_down"].astype(x.dtype), state
    if collect:
        # freeze across padding: f'=1 (logf=0), i'=0 (logi=-inf); neutralize
        # the pos==0 reset at padding slots
        vmask = _valid(ctx, x)
        valid = vmask[..., None]
        logf = jnp.where(valid, logf, 0.0)
        logi = jnp.where(valid, logi, -1e30)
        pos_nz = jnp.where(vmask, ctx.positions, 1)
        y, (C, n, m) = mlstm(q, k, v, logf, logi, positions=pos_nz,
                             chunk=cfg.scan_chunk, return_state=True)
        lens = _valid(ctx, x).sum(-1)
        state = {"conv": _conv_tail(x_in, lens, cfg.conv_width),
                 "C": C, "n": n, "m": m}
        y = y.reshape(B, L, pf) * jax.nn.silu(z)
        return x + y @ p["w_down"].astype(x.dtype), state
    y = mlstm(q, k, v, logf, logi, positions=ctx.positions,
              chunk=cfg.scan_chunk)
    y = y.reshape(B, L, pf) * jax.nn.silu(z)
    return x + y @ p["w_down"].astype(x.dtype)


def init_mlstm_cache(cfg: ArchConfig, batch: int, dtype):
    H = cfg.n_heads
    pf = int(cfg.proj_factor * cfg.d_model)
    dh = pf // H
    return {"conv": jnp.zeros((batch, cfg.conv_width - 1, pf), dtype),
            "C": jnp.zeros((batch, H, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, H, dh), jnp.float32),
            "m": jnp.full((batch, H), -1e30, jnp.float32)}


def step_mlstm(p, x_t, cache, ctx: Ctx, cfg: ArchConfig):
    B = x_t.shape[0]
    H = cfg.n_heads
    pf = p["w_upx"].shape[1]
    dh = pf // H
    hin = _norm(p["norm"], x_t, cfg.norm_eps)
    x_in = hin[:, 0] @ p["w_upx"].astype(hin.dtype)
    z = hin[:, 0] @ p["w_upz"].astype(hin.dtype)
    x_c, conv_state = conv1d_pack_update(
        x_in, cache["conv"], p["conv_w"].astype(hin.dtype),
        p["conv_b"].astype(hin.dtype), ctx.reset_t)
    x_c = jax.nn.silu(x_c)
    q = (x_c @ p["wq"].astype(hin.dtype)).reshape(B, H, dh)
    k = (x_c @ p["wk"].astype(hin.dtype)).reshape(B, H, dh)
    v = (x_in @ p["wv"].astype(hin.dtype)).reshape(B, H, dh)
    g = x_c @ p["w_if"].astype(hin.dtype) + p["b_if"].astype(hin.dtype)
    logi, f_pre = jnp.split(g, 2, axis=-1)
    logf = jax.nn.log_sigmoid(f_pre.astype(jnp.float32))
    y, (C, n, m) = mlstm_step((cache["C"], cache["n"], cache["m"]),
                              q, k, v, logf, logi.astype(jnp.float32),
                              ctx.reset_t)
    y = y.reshape(B, pf) * jax.nn.silu(z)
    out = y @ p["w_down"].astype(x_t.dtype)
    return x_t + out[:, None], {"conv": conv_state, "C": C, "n": n, "m": m}


def init_slstm(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, H = cfg.d_model, cfg.n_heads
    dh = d // H
    ks = jax.random.split(key, 3)
    return {
        "norm": jnp.ones((d,)),
        "w_pre": _dense(ks[0], d, 4 * d),
        "R": jax.random.normal(ks[1], (4, H, dh, dh)) * dh ** -0.5 * 0.3,
        "w_out": _dense(ks[2], d, d),
    }


def apply_slstm(p, x, ctx: Ctx, cfg: ArchConfig, collect: int = 0,
                collect_ends=None):
    B, L, d = x.shape
    H = cfg.n_heads
    dh = d // H
    h = _norm(p["norm"], x, cfg.norm_eps)
    pre = (h @ p["w_pre"].astype(h.dtype)).reshape(B, L, 4, H, dh)
    if collect and collect_ends is not None:
        # per-SEGMENT handoff: sLSTM is inherently sequential, so vmap its
        # existing valid-freeze over segments (state frozen outside segment
        # g ⇒ row-final state = state at g's last token).
        y = slstm(pre, p["R"], positions=ctx.positions)

        def one_seg(sid):
            msk = ctx.segment_ids == sid
            _, st = slstm(pre, p["R"],
                          positions=jnp.where(msk, ctx.positions, 1),
                          valid=msk, return_state=True)
            return st

        nseg = collect_ends.shape[1]
        cs, ns, ms, hs = jax.vmap(one_seg, out_axes=1)(
            jnp.arange(1, nseg + 1, dtype=jnp.int32))
        out = x + y.reshape(B, L, d) @ p["w_out"].astype(x.dtype)
        return out, {"c": cs, "n": ns, "m": ms, "h": hs}
    if collect:
        y, (c, n, m, hh) = slstm(pre, p["R"], positions=ctx.positions,
                                 valid=_valid(ctx, x), return_state=True)
        out = x + y.reshape(B, L, d) @ p["w_out"].astype(x.dtype)
        return out, {"c": c, "n": n, "m": m, "h": hh}
    y = slstm(pre, p["R"], positions=ctx.positions)
    y = y.reshape(B, L, d) @ p["w_out"].astype(x.dtype)
    return x + y


def init_slstm_cache(cfg: ArchConfig, batch: int, dtype):
    H = cfg.n_heads
    dh = cfg.d_model // H
    z = jnp.zeros((batch, H, dh), jnp.float32)
    return {"c": z, "n": z, "m": jnp.full((batch, H, dh), -1e30, jnp.float32),
            "h": z}


def step_slstm(p, x_t, cache, ctx: Ctx, cfg: ArchConfig):
    B = x_t.shape[0]
    H = cfg.n_heads
    d = cfg.d_model
    dh = d // H
    h = _norm(p["norm"], x_t, cfg.norm_eps)
    pre = (h[:, 0] @ p["w_pre"].astype(h.dtype)).reshape(B, 1, 4, H, dh)
    st = (cache["c"], cache["n"], cache["m"], cache["h"])
    pos = None
    if ctx.reset_t is not None:
        pos = jnp.where(ctx.reset_t, 0, 1)[:, None]      # (B,1): 0 ⇒ reset
    y, (c, n, m, hh) = slstm(pre, p["R"], positions=pos, state=st,
                             return_state=True)
    out = y.reshape(B, d) @ p["w_out"].astype(x_t.dtype)
    return x_t + out[:, None], {"c": c, "n": n, "m": m, "h": hh}


# ===========================================================================
# chunk-resume prefill steps
# ===========================================================================
# ``chunk_<kind>(p, x, cache, ctx, cfg) -> (x, cache)`` consumes a (B, T, d)
# slab of a LONG prompt and advances the DECODE-layout cache in place — the
# O(1) recurrent state makes resumable prefill natural (no KV re-read; the
# attention ring is the one windowed structure, handled below). Protocol:
#   ctx.positions    (B, T) GLOBAL intra-sequence positions (off + t);
#                    padding slots hold anything (they are neutralized)
#   ctx.segment_ids  (B, T) 1 = real token, 0 = padding (trailing only —
#                    one request per chunk row, never packed)
#   ctx.cache_len    (B,) tokens already consumed before this chunk
# Rows whose slab is all padding are exact state no-ops (freeze semantics:
# Δ=0 ⇒ Ā=1, B̄x=0 — the same trick the per-row collect paths use).


def _conv_resume(x_in, conv_cache, w, b, positions, backend):
    """Causal conv over a resumed chunk: prepend the cached (W-1)-tail,
    run conv1d_pack, drop the warm-up outputs. Tap validity depends only on
    the OUTPUT position, so extending positions with W-1 leading zeros
    leaves every kept output exact. Returns (x_c (B, T, D), new tail)."""
    B, T, D = x_in.shape
    W = w.shape[0]
    ext = jnp.concatenate([conv_cache.astype(x_in.dtype), x_in], axis=1)
    pos_ext = jnp.concatenate(
        [jnp.zeros((B, W - 1), positions.dtype), positions], axis=1)
    x_c = kops.conv1d_pack(ext, w, b, pos_ext, backend=backend)[:, W - 1:]
    return x_c


def chunk_mamba(p, x, cache, ctx: Ctx, cfg: ArchConfig):
    W = cfg.d_conv
    backend = "pallas" if cfg.use_pallas else "xla"
    dt = x.dtype
    x_in, z = _mamba_in(p, x, cfg)
    with jax.named_scope("mamba.conv"):
        x_c = _conv_resume(x_in, cache["conv"], p["conv_w"].astype(dt),
                           p["conv_b"].astype(dt), ctx.positions, backend)
        x_c = jax.nn.silu(x_c)
    delta, Bm, Cm = _mamba_gates(p, x_c, dt, cfg)
    with jax.named_scope("mamba.scan"):
        A = -jnp.exp(p["A_log"])
        valid = _valid(ctx, x)
        delta = delta * valid[..., None].astype(delta.dtype)
        pos_nz = jnp.where(valid, ctx.positions, 1)
        y, h_last = core_ssm.selective_scan(
            x_c, delta, A, Bm, Cm, p["D"], positions=pos_nz,
            method=cfg.scan_impl, chunk=cfg.scan_chunk, return_state=True,
            h0=cache["ssm"], intra=cfg.scan_intra)
    with jax.named_scope("mamba.conv"):
        ext = jnp.concatenate([cache["conv"].astype(x_in.dtype), x_in],
                              axis=1)
        conv = _conv_tail(ext, (W - 1) + valid.sum(-1), W)
    return _mamba_out(p, x, y, z), {"conv": conv, "ssm": h_last}


def chunk_mamba2(p, x, cache, ctx: Ctx, cfg: ArchConfig):
    B, T, d = x.shape
    di, H, P, W = cfg.d_inner, cfg.n_ssm_heads, cfg.ssm_hd, cfg.d_conv
    backend = "pallas" if cfg.use_pallas else "xla"
    h = _norm(p["norm"], x, cfg.norm_eps)
    xz = h @ p["in_proj"].astype(h.dtype)
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_c = _conv_resume(x_in, cache["conv"], p["conv_w"].astype(h.dtype),
                       p["conv_b"].astype(h.dtype), ctx.positions, backend)
    x_c = jax.nn.silu(x_c)
    delta, Bm, Cm = _mamba2_gates(p, x_c, cfg)
    A = -jnp.exp(p["A_log"])
    valid = _valid(ctx, x)
    delta = delta * valid[..., None].astype(delta.dtype)
    pos_nz = jnp.where(valid, ctx.positions, 1)
    y, h_last = core_ssm.selective_scan_heads(
        x_c.reshape(B, T, H, P), delta, A, Bm, Cm, p["D"],
        positions=pos_nz, method="blocked", chunk=cfg.scan_chunk,
        return_state=True, h0=cache["ssm"], intra=cfg.scan_intra)
    ext = jnp.concatenate([cache["conv"].astype(x_in.dtype), x_in], axis=1)
    state = {"conv": _conv_tail(ext, (W - 1) + valid.sum(-1), W),
             "ssm": h_last}
    y = _mamba2_gate_out(p, y.reshape(B, T, di), z, cfg)
    return x + y @ p["out_proj"].astype(x.dtype), state


def chunk_rec(p, x, cache, ctx: Ctx, cfg: ArchConfig):
    backend = "pallas" if cfg.use_pallas else "xla"
    nb = cfg.lru_gate_blocks
    W = cfg.conv_width
    h = _norm(p["norm"], x, cfg.norm_eps)
    y_branch = jax.nn.gelu(h @ p["w_y"].astype(h.dtype))
    x_branch = h @ p["w_x"].astype(h.dtype)
    x_c = _conv_resume(x_branch, cache["conv"], p["conv_w"].astype(h.dtype),
                       p["conv_b"].astype(h.dtype), ctx.positions, backend)
    r = jax.nn.sigmoid(_gate_blockdiag(x_c, p["w_r"].astype(h.dtype), nb))
    i = jax.nn.sigmoid(_gate_blockdiag(x_c, p["w_i"].astype(h.dtype), nb))
    vmask = _valid(ctx, x)
    valid = vmask[..., None].astype(r.dtype)
    r, i = r * valid, i * valid
    pos_nz = jnp.where(vmask, ctx.positions, 1)
    cdt = None if cfg.scan_dtype == "float32" else cfg.scan_dtype
    lru, h_last = rglru(x_c, r, i, p["a_param"], pos_nz, h0=cache["h"],
                        method="chunked", chunk=cfg.scan_chunk,
                        compute_dtype=cdt)
    out = (lru * y_branch) @ p["wo"].astype(x.dtype)
    ext = jnp.concatenate([cache["conv"].astype(x_branch.dtype), x_branch],
                          axis=1)
    return x + out, {"conv": _conv_tail(ext, (W - 1) + vmask.sum(-1), W),
                     "h": h_last}


def chunk_attn(p, x, cache, ctx: Ctx, cfg: ArchConfig):
    """Chunked prefill into the ring-buffer KV cache: attend (one joint
    softmax over the cached prefix ring and the intra-chunk causal keys),
    THEN write the chunk's post-rope K/V into its ring slots — the write
    may evict prefix slots the chunk itself still needed, so order matters.
    Requires chunk T ≤ ring size S (the engine sizes chunks to fit)."""
    B, T, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    S = cache["k"].shape[1]
    if T > S:
        raise ValueError(f"chunk length {T} exceeds attention cache/window "
                         f"{S} — use a chunk size ≤ the attention window")
    G = H // Hkv
    h = _norm(p["norm"], x, cfg.norm_eps)
    q = (h @ p["wq"].astype(h.dtype)).reshape(B, T, H, hd)
    kv = (h @ p["wkv"].astype(h.dtype)).reshape(B, T, 2, Hkv, hd)
    k, v = kv[:, :, 0], kv[:, :, 1]
    q, k = _apply_rope(cfg, q, k, ctx)
    valid = _valid(ctx, x)                               # (B, T)
    pos = ctx.positions                                  # (B, T) global
    clen = ctx.cache_len[:, None]                        # (B, 1)
    # prefix ring: slot s holds token t_s = s + ((clen-1-s)//S)·S < clen
    s_idx = jnp.arange(S)[None, :]
    t_s = s_idx + ((clen - 1 - s_idx) // S) * S          # (B, S)
    pref_ok = (s_idx < clen) & (t_s >= 0)
    qr = q.reshape(B, T, Hkv, G, hd)
    sc_pre = jnp.einsum("btkgd,bskd->btkgs", qr, cache["k"],
                        preferred_element_type=jnp.float32) * hd ** -0.5
    m_pre = pref_ok[:, None, :]                          # (B, 1, S)
    if cfg.attn_window is not None:
        m_pre = m_pre & (t_s[:, None, :] >
                         pos[:, :, None] - cfg.attn_window)
    sc_pre = jnp.where(m_pre[:, :, None, None, :], sc_pre, -1e30)
    # intra-chunk: causal over the slab, windowed, padding keys excluded
    sc_in = jnp.einsum("btkgd,bjkd->btkgj", qr, k,
                       preferred_element_type=jnp.float32) * hd ** -0.5
    m_in = (pos[:, :, None] >= pos[:, None, :]) & valid[:, None, :]
    m_in = m_in & (jnp.arange(T)[None, :, None] >= jnp.arange(T)[None, None])
    if cfg.attn_window is not None:
        m_in = m_in & (pos[:, None, :] > pos[:, :, None] - cfg.attn_window)
    sc_in = jnp.where(m_in[:, :, None, None, :], sc_in, -1e30)
    sc = jnp.concatenate([sc_pre, sc_in], axis=-1)       # (B, T, Hkv, G, S+T)
    pr = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    o = (jnp.einsum("btkgs,bskd->btkgd", pr[..., :S], cache["v"]) +
         jnp.einsum("btkgj,bjkd->btkgd", pr[..., S:], v))
    o = o.reshape(B, T, H * hd) @ p["wo"].astype(x.dtype)
    # write AFTER attending: valid chunk tokens land at pos % S (distinct
    # because T ≤ S), padding routes to the drop sentinel S
    slot = jnp.where(valid, pos % S, S)
    bidx = jnp.arange(B)[:, None]
    kc = cache["k"].at[bidx, slot].set(k, mode="drop")
    vc = cache["v"].at[bidx, slot].set(v, mode="drop")
    return x + o, {"k": kc, "v": vc}


def chunk_mlstm(p, x, cache, ctx: Ctx, cfg: ArchConfig):
    B, T, d = x.shape
    H = cfg.n_heads
    pf = p["w_upx"].shape[1]
    dh = pf // H
    W = cfg.conv_width
    backend = "pallas" if cfg.use_pallas else "xla"
    hin = _norm(p["norm"], x, cfg.norm_eps)
    x_in = hin @ p["w_upx"].astype(hin.dtype)
    z = hin @ p["w_upz"].astype(hin.dtype)
    x_c = _conv_resume(x_in, cache["conv"], p["conv_w"].astype(hin.dtype),
                       p["conv_b"].astype(hin.dtype), ctx.positions, backend)
    x_c = jax.nn.silu(x_c)
    q = (x_c @ p["wq"].astype(hin.dtype)).reshape(B, T, H, dh)
    k = (x_c @ p["wk"].astype(hin.dtype)).reshape(B, T, H, dh)
    v = (x_in @ p["wv"].astype(hin.dtype)).reshape(B, T, H, dh)
    g = x_c @ p["w_if"].astype(hin.dtype) + p["b_if"].astype(hin.dtype)
    logi, f_pre = jnp.split(g, 2, axis=-1)
    logf = jax.nn.log_sigmoid(f_pre.astype(jnp.float32))
    logi = logi.astype(jnp.float32)
    vmask = _valid(ctx, x)
    valid = vmask[..., None]
    logf = jnp.where(valid, logf, 0.0)
    logi = jnp.where(valid, logi, -1e30)
    pos_nz = jnp.where(vmask, ctx.positions, 1)
    y, (C, n, m) = mlstm(q, k, v, logf, logi, positions=pos_nz,
                         chunk=cfg.scan_chunk,
                         state=(cache["C"], cache["n"], cache["m"]),
                         return_state=True)
    ext = jnp.concatenate([cache["conv"].astype(x_in.dtype), x_in], axis=1)
    state = {"conv": _conv_tail(ext, (W - 1) + vmask.sum(-1), W),
             "C": C, "n": n, "m": m}
    y = y.reshape(B, T, pf) * jax.nn.silu(z)
    return x + y @ p["w_down"].astype(x.dtype), state


def chunk_slstm(p, x, cache, ctx: Ctx, cfg: ArchConfig):
    B, T, d = x.shape
    H = cfg.n_heads
    dh = d // H
    h = _norm(p["norm"], x, cfg.norm_eps)
    pre = (h @ p["w_pre"].astype(h.dtype)).reshape(B, T, 4, H, dh)
    st = (cache["c"], cache["n"], cache["m"], cache["h"])
    y, (c, n, m, hh) = slstm(pre, p["R"], positions=ctx.positions,
                             state=st, valid=_valid(ctx, x),
                             return_state=True)
    out = x + y.reshape(B, T, d) @ p["w_out"].astype(x.dtype)
    return out, {"c": c, "n": n, "m": m, "h": hh}


CHUNK = {"attn": chunk_attn, "mamba": chunk_mamba, "mamba2": chunk_mamba2,
         "rec": chunk_rec, "mlstm": chunk_mlstm, "slstm": chunk_slstm}


# ===========================================================================
# batched sampling (serving decode)
# ===========================================================================
# Key plumbing is raw-uint32 (B, 2) arrays so per-slot keys live as ordinary
# pytree leaves inside jitted engine steps (scatter/carry like any other slot
# state); `request_keys` derives a request's stream from (seed, rid) so a
# request samples identically wherever its slot lands.

def request_keys(seed, rids):
    """Per-request PRNG keys: fold each rid into a base seed. rids (K,) int32
    → (K, 2) uint32 key batch."""
    base = jax.random.PRNGKey(seed)
    return jax.vmap(lambda r: jax.random.fold_in(base, r))(
        jnp.asarray(rids, jnp.int32))


def split_keys(keys):
    """Advance a (B, 2) key batch one step: returns (carry, subkey), each
    (B, 2) uint32."""
    pairs = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return pairs[:, 0], pairs[:, 1]


def sample_from_logits(logits, keys, temperature, top_k, top_p):
    """Fixed-shape batched sampling over decode slots.

    logits (B, V) f32; keys (B, 2) uint32 per-slot PRNG carry;
    temperature (B,) f32 — ``<= 0`` means GREEDY (argmax, key unused but
    still advanced so slot streams stay aligned); top_k (B,) int32 — keep
    the k highest logits (``<= 0`` disables); top_p (B,) f32 — keep the
    smallest prefix of the sorted distribution with mass ≥ top_p
    (``>= 1`` disables). All three are per-slot so one jitted step serves a
    mixed batch. Returns (tokens (B,) int32, new_keys (B, 2) uint32).
    """
    V = logits.shape[-1]
    lg = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    sorted_lg = -jnp.sort(-lg, axis=-1)                       # descending
    # top-k: threshold at the k-th largest logit (k<=0 → full vocab)
    k = jnp.where(top_k > 0, top_k, V).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_lg,
                              jnp.clip(k - 1, 0, V - 1)[:, None], axis=-1)
    masked = jnp.where(lg >= kth, lg, -jnp.inf)
    # top-p (nucleus): keep sorted tokens while the mass BEFORE them < p —
    # always keeps at least the argmax; threshold back onto unsorted logits
    probs = jax.nn.softmax(sorted_lg, axis=-1)
    before = jnp.cumsum(probs, axis=-1) - probs
    nkeep = (before < jnp.clip(top_p, 0.0, 1.0)[:, None]).sum(-1)
    pth = jnp.take_along_axis(sorted_lg,
                              jnp.clip(nkeep - 1, 0, V - 1)[:, None], axis=-1)
    masked = jnp.where(lg >= pth, masked, -jnp.inf)
    carry, sub = split_keys(keys)
    gumbel = jax.vmap(lambda kk: jax.random.gumbel(kk, (V,), jnp.float32))(
        sub)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jnp.argmax(masked / temp + gumbel, axis=-1).astype(jnp.int32)
    tok = jnp.where(temperature > 0.0, sampled, greedy_tok)
    return tok, carry


# ===========================================================================
# kind registry
# ===========================================================================

INIT = {"attn": init_attn, "mlp": init_mlp, "moe": init_moe,
        "mamba": init_mamba, "mamba2": init_mamba2, "rec": init_rec,
        "mlstm": init_mlstm, "slstm": init_slstm}

CACHE_INIT = {"attn": init_attn_cache, "mamba": init_mamba_cache,
              "mamba2": init_mamba2_cache, "rec": init_rec_cache,
              "mlstm": init_mlstm_cache, "slstm": init_slstm_cache}

STEP = {"attn": step_attn, "mamba": step_mamba, "mamba2": step_mamba2,
        "rec": step_rec, "mlstm": step_mlstm, "slstm": step_slstm}
