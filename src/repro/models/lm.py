"""Model assembly: embedding → scan-over-pattern-units → norm → head.

Heterogeneous stacks (RecurrentGemma's rec/rec/attn, xLSTM's mLSTM/sLSTM
mix) are expressed as a repeating *unit*; whole units are stacked and
lax.scan'ed (compact HLO, O(1) compile time in depth), any remainder layers
run unstacked. ``remat="unit"`` wraps each unit in jax.checkpoint.

Three entry points per model, matching the assigned shapes:
  * ``loss``/``forward``    — packed training fwd (train_4k)
  * ``prefill``             — packed fwd that also collects decode caches and
                              per-row cursor (prefill_32k)
  * ``decode_step``         — one token against the cache (decode_32k,
                              long_500k)

Packing-awareness is uniform: every sequence-wise sub-block receives
``positions``/``segment_ids`` and applies the paper's boundary rules.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks as B
from repro.models.blocks import Ctx


# ---------------------------------------------------------------------------
# unit layout
# ---------------------------------------------------------------------------

def unit_layout(cfg: ArchConfig) -> Tuple[Tuple[str, str], ...]:
    """(param_name, kind) pairs for one pattern unit."""
    out: List[Tuple[str, str]] = []
    for i, kind in enumerate(cfg.unit):
        if kind == "attn":
            out.append((f"{i}_attn", "attn"))
            if cfg.d_ff:
                out.append((f"{i}_ffn", "mlp"))
        elif kind == "moe_attn":
            out.append((f"{i}_attn", "attn"))
            out.append((f"{i}_moe", "moe"))
        elif kind == "rec":
            out.append((f"{i}_rec", "rec"))
            if cfg.d_ff:
                out.append((f"{i}_ffn", "mlp"))
        elif kind == "mamba":
            out.append((f"{i}_mamba", "mamba"))
        elif kind == "mamba2":
            out.append((f"{i}_mamba2", "mamba2"))
        elif kind == "mlstm":
            out.append((f"{i}_mlstm", "mlstm"))
            if cfg.d_ff:
                out.append((f"{i}_ffn", "mlp"))
        elif kind == "slstm":
            out.append((f"{i}_slstm", "slstm"))
            if cfg.d_ff:
                out.append((f"{i}_ffn", "mlp"))
        else:
            raise ValueError(f"unknown unit kind {kind!r}")
    return tuple(out)


_APPLY = {"attn": B.apply_attn, "mlp": B.apply_mlp, "moe": B.apply_moe,
          "mamba": B.apply_mamba, "mamba2": B.apply_mamba2,
          "rec": B.apply_rec, "mlstm": B.apply_mlstm, "slstm": B.apply_slstm}


def _apply_sub(kind, p, x, ctx, cfg, collect: int = 0, collect_ends=None):
    """Uniform (x, aux, state) return. ``collect`` (= cache max_len when
    nonzero) asks state-bearing blocks to also emit their decode cache —
    per row, or per packed segment when ``collect_ends`` (B, S) is given."""
    if kind in ("mlp", "moe"):
        out = _APPLY[kind](p, x, ctx, cfg)
        if kind == "moe":
            return out[0], out[1], None
        return out, None, None
    if collect:
        x, state = _APPLY[kind](p, x, ctx, cfg, collect=collect,
                                collect_ends=collect_ends)
        return x, None, state
    return _APPLY[kind](p, x, ctx, cfg), None, None


class LM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.layout = unit_layout(cfg)
        self.n_units = cfg.n_layers // len(cfg.unit)
        self.n_tail = cfg.n_layers % len(cfg.unit)
        # tail layers reuse the unit layout prefix
        self.tail_layout = unit_layout(cfg)[:self._tail_sublocks()] \
            if self.n_tail else ()

    def _tail_sublocks(self) -> int:
        # count sub-blocks belonging to the first n_tail layers of the unit
        n = 0
        for name, kind in self.layout:
            layer_idx = int(name.split("_")[0])
            if layer_idx < self.n_tail:
                n += 1
        return n

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        k_embed, k_units, k_tail, k_head = jax.random.split(key, 4)
        params: Dict[str, Any] = {}
        if cfg.family == "audio":
            params["input_proj"] = B._dense(k_embed, cfg.d_model, cfg.d_model)
        else:
            params["embed"] = jax.random.normal(
                k_embed, (cfg.vocab, cfg.d_model)) * 0.02

        def unit_init(k):
            p = {}
            ks = jax.random.split(k, len(self.layout))
            for kk, (name, kind) in zip(ks, self.layout):
                p[name] = B.INIT[kind](kk, cfg)
            return p

        if self.n_units:
            params["units"] = jax.vmap(unit_init)(
                jax.random.split(k_units, self.n_units))
        if self.n_tail:
            p = {}
            ks = jax.random.split(k_tail, len(self.tail_layout))
            for kk, (name, kind) in zip(ks, self.tail_layout):
                p[name] = B.INIT[kind](kk, cfg)
            params["tail"] = p
        params["final_norm"] = jnp.ones((cfg.d_model,))
        if not cfg.tie_embeddings:
            params["head"] = B._dense(k_head, cfg.d_model, cfg.vocab)
        pdt = jnp.dtype(cfg.param_dtype)
        if pdt != jnp.float32:
            # low-precision storage: cast float leaves only (optim keeps f32
            # masters; blocks re-cast at use via the .astype(h.dtype) idiom)
            params = jax.tree.map(
                lambda x: x.astype(pdt)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        return params

    # ------------------------------------------------------------- embedding
    @jax.named_scope("lm.embed")
    def _embed(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        if cfg.family == "audio":
            x = batch["frames"].astype(dt) @ params["input_proj"].astype(dt)
            return x
        x = self._lookup(params, batch["tokens"])
        if cfg.family == "vlm" and "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(dt)          # (B, Nv, d)
            vp = batch["vision_positions"]                  # (B, Nv) i32
            bidx = jnp.arange(x.shape[0])[:, None]
            x = x.at[bidx, vp].set(ve)
        return x

    def _lookup(self, params, tokens) -> jnp.ndarray:
        """Embedding rows of ``tokens`` in the compute dtype, scaled by
        sqrt(d_model) where the published model does so (``embed_scale``)."""
        dt = jnp.dtype(self.cfg.dtype)
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        if self.cfg.embed_scale:
            x = x * jnp.asarray(self.cfg.d_model ** 0.5, dt)
        return x

    def _ctx(self, batch) -> Ctx:
        return Ctx(positions=batch.get("positions"),
                   segment_ids=batch.get("segment_ids"),
                   mrope_positions=batch.get("mrope_positions"))

    # ----------------------------------------------------------- forward
    def _stack(self, params, x, ctx) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        """Run all layers. Returns (hidden, aux)."""
        cfg = self.cfg

        def constrain(x):
            # Megatron-SP analogue: the residual carried (and saved for
            # backward) between units is sequence-sharded over "model";
            # XLA re-gathers at TP matmuls and reduce-scatters afterwards.
            if cfg.act_pspec is not None:
                from jax.sharding import PartitionSpec as P
                x = jax.lax.with_sharding_constraint(x, P(*cfg.act_pspec))
            return x

        def unit_body(carry, unit_p):
            x, lb, zl = carry
            for name, kind in self.layout:
                x, aux, _ = _apply_sub(kind, unit_p[name], x, ctx, cfg)
                if aux:
                    lb = lb + aux["lb_loss"]
                    zl = zl + aux["z_loss"]
            return (constrain(x), lb, zl), None

        if cfg.remat == "unit":
            unit_body = jax.checkpoint(unit_body)
        elif cfg.remat == "dots":
            # save matmul outputs, recompute elementwise only — trades the
            # HBM headroom won by act_sp/accum for less recompute traffic
            unit_body = jax.checkpoint(
                unit_body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        lb = jnp.zeros((), jnp.float32)
        zl = jnp.zeros((), jnp.float32)
        x = constrain(x)
        if self.n_units:
            (x, lb, zl), _ = jax.lax.scan(unit_body, (x, lb, zl),
                                          params["units"])
        if self.n_tail:
            for name, kind in self.tail_layout:
                x, aux, _ = _apply_sub(kind, params["tail"][name], x, ctx,
                                       cfg)
                if aux:
                    lb = lb + aux["lb_loss"]
                    zl = zl + aux["z_loss"]
        with jax.named_scope("lm.head"):
            x = B._norm(params["final_norm"], x, cfg.norm_eps)
        return x, {"lb_loss": lb, "z_loss": zl}

    def _head_t(self, params):
        cfg = self.cfg
        w = params["embed"].T if cfg.tie_embeddings else params["head"]
        return w

    def forward(self, params, batch) -> jnp.ndarray:
        """Full logits (B, L, V) — small models / tests only."""
        x = self._embed(params, batch)
        x, _ = self._stack(params, x, self._ctx(batch))
        return (x @ self._head_t(params).astype(x.dtype)).astype(jnp.float32)

    # ----------------------------------------------------------- loss
    def loss(self, params, batch, loss_chunk: int = 512):
        """Packed next-token CE. Labels: explicit batch['labels'] (with -1 =
        masked) or derived by in-segment shift. Vocab-dim logits are computed
        in L-chunks so the (B, L, V) f32 tensor never materializes."""
        x = self._embed(params, batch)
        x, aux = self._stack(params, x, self._ctx(batch))
        with jax.named_scope("lm.head"):
            return self._head_loss(params, x, aux, batch, loss_chunk)

    def _head_loss(self, params, x, aux, batch, loss_chunk: int):
        """The head and the loss over the final hidden states ``x``."""
        cfg = self.cfg
        if "labels" in batch:
            labels = batch["labels"]
        else:
            seg = batch["segment_ids"]
            tok = batch["tokens"]
            nxt_same = (seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] != 0)
            labels = jnp.where(nxt_same, tok[:, 1:], -1)
            labels = jnp.concatenate(
                [labels, jnp.full((labels.shape[0], 1), -1, labels.dtype)],
                axis=1)
        Bz, L, d = x.shape
        W = self._head_t(params)
        nchunk = max(1, L // min(loss_chunk, L))
        if L % nchunk:
            nchunk = 1
        xs = x.reshape(Bz, nchunk, L // nchunk, d)
        ls = labels.reshape(Bz, nchunk, L // nchunk)

        def chunk_ce(args):
            xc, lc = args                                  # (B, C, d), (B, C)
            logits = (xc @ W.astype(xc.dtype)).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
            nll = lse - gold
            mask = (lc >= 0).astype(jnp.float32)
            return (nll * mask).sum(), mask.sum()

        tot, cnt = jax.lax.map(chunk_ce, (jnp.moveaxis(xs, 1, 0),
                                          jnp.moveaxis(ls, 1, 0)))
        loss = tot.sum() / jnp.maximum(cnt.sum(), 1.0)
        if cfg.n_experts:
            loss = loss + 0.01 * aux["lb_loss"] + 0.001 * aux["z_loss"]
        metrics = {"ce": tot.sum() / jnp.maximum(cnt.sum(), 1.0),
                   "tokens": cnt.sum(), **aux}
        return loss, metrics

    def prefill_logits(self, params, batch):
        """Serving prefill: logits at each row's last valid position.
        (The dry-run prefill cell lowers this — the forward pass dominates
        its roofline; `prefill` below additionally hands off caches.)"""
        x = self._embed(params, batch)
        x, _ = self._stack(params, x, self._ctx(batch))
        lens = (batch["segment_ids"] > 0).sum(-1)           # (B,)
        xlast = x[jnp.arange(x.shape[0]), jnp.maximum(lens - 1, 0)]
        W = self._head_t(params)
        return (xlast @ W.astype(xlast.dtype)).astype(jnp.float32)

    def prefill(self, params, batch, max_len: int):
        """Full serving prefill: one forward pass over a batch of
        left-aligned prompts (one sequence per row; segment_ids mark
        validity) that also collects every layer's decode cache — O(L)
        handoff instead of token replay. Recurrent states are frozen across
        right-padding (Δ=0 / gate masking / slstm freeze) so the handed-off
        state is exactly the state after each row's last valid token.

        Returns (last_logits (B, V), cache, cache_len (B,))."""
        cfg = self.cfg
        x = self._embed(params, batch)
        ctx = self._ctx(batch)
        lens = (batch["segment_ids"] > 0).sum(-1)

        def unit_body(x, unit_p):
            states = {}
            for name, kind in self.layout:
                x, _, st = _apply_sub(kind, unit_p[name], x, ctx, cfg,
                                      collect=max_len)
                if st is not None:
                    states[name] = st
            return x, states

        cache: Dict[str, Any] = {}
        if self.n_units:
            x, unit_states = jax.lax.scan(unit_body, x, params["units"])
            cache["units"] = unit_states
        if self.n_tail:
            tail_states = {}
            for name, kind in self.tail_layout:
                x, _, st = _apply_sub(kind, params["tail"][name], x, ctx,
                                      cfg, collect=max_len)
                if st is not None:
                    tail_states[name] = st
            cache["tail"] = tail_states
        with jax.named_scope("lm.head"):
            x = B._norm(params["final_norm"], x, cfg.norm_eps)
            xlast = x[jnp.arange(x.shape[0]), jnp.maximum(lens - 1, 0)]
            W = self._head_t(params)
            logits = (xlast @ W.astype(xlast.dtype)).astype(jnp.float32)
        return logits, cache, lens

    def prefill_packed(self, params, batch, max_len: int, ends):
        """Packed multi-prompt prefill: ONE forward over PACKED rows (many
        prompts laid back-to-back per row, core/packing.py layout) that
        hands off a decode cache for EVERY packed segment — the
        continuous-batching admission path. ``ends`` (B, S) int32 is each
        segment's last-token index in its row (−1 = absent segment; S is the
        static per-row segment capacity).

        The paper's reset rule makes each segment's state independent of its
        neighbors, so per-segment finals are trajectory samples at ``ends``
        (see models/blocks.py docstring) — no replay, no per-prompt rows.

        Returns (logits (B, S, V) at segment ends, states pytree whose
        leaves carry (B, S, …) leading dims ((n_units, B, S, …) for
        unit-stacked layers), seg_lens (B, S) int32 — 0 where absent).
        Feed the states to ``scatter_into_cache`` to land them in decode
        slots."""
        cfg = self.cfg
        x = self._embed(params, batch)
        ctx = self._ctx(batch)

        def unit_body(x, unit_p):
            states = {}
            for name, kind in self.layout:
                x, _, st = _apply_sub(kind, unit_p[name], x, ctx, cfg,
                                      collect=max_len, collect_ends=ends)
                if st is not None:
                    states[name] = st
            return x, states

        states: Dict[str, Any] = {}
        if self.n_units:
            x, unit_states = jax.lax.scan(unit_body, x, params["units"])
            states["units"] = unit_states
        if self.n_tail:
            tail_states = {}
            for name, kind in self.tail_layout:
                x, _, st = _apply_sub(kind, params["tail"][name], x, ctx,
                                      cfg, collect=max_len, collect_ends=ends)
                if st is not None:
                    tail_states[name] = st
            states["tail"] = tail_states
        with jax.named_scope("lm.head"):
            x = B._norm(params["final_norm"], x, cfg.norm_eps)
            Bsz, L, d = x.shape
            S = ends.shape[1]
            idx = jnp.clip(ends, 0, L - 1)[..., None]
            xe = jnp.take_along_axis(
                x, jnp.broadcast_to(idx, (Bsz, S, d)), axis=1)
            W = self._head_t(params)
            logits = (xe @ W.astype(xe.dtype)).astype(jnp.float32)
            logits = jnp.where((ends >= 0)[..., None], logits, 0.0)
        seg_lens = B._ends_lens(ctx, ends)
        return logits, states, seg_lens

    # -------------------------------------------------- chunk-resume prefill
    @property
    def supports_chunked_prefill(self) -> bool:
        """True when every state-bearing sub-block has a chunk-resume step
        (``blocks.CHUNK``) — the serve engine's gate for accepting prompts
        longer than its largest prefill bucket."""
        if self.cfg.family in ("audio", "vlm"):
            return False
        return all(kind in B.CHUNK or kind in ("mlp", "moe")
                   for _, kind in self.layout)

    def prefill_chunk(self, params, cache, batch, cache_len):
        """Advance DECODE-layout caches by one (B, T) slab of long prompts
        — resumable prefill from the carried O(1) state, so a prompt far
        beyond any prefill bucket is consumed in fixed-shape chunks while
        decode slots keep stepping. ``batch`` holds tokens/positions/
        segment_ids for the slab (positions GLOBAL, segment_ids 0 marks
        trailing padding — all-padding rows are exact state no-ops);
        ``cache_len`` (B,) counts tokens already consumed. Returns
        (logits (B, V) at each row's last valid slab token, new_cache,
        new cache_len)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        ctx = Ctx(positions=batch.get("positions"),
                  segment_ids=batch.get("segment_ids"),
                  cache_len=cache_len)

        def unit_step(x, unit_p, unit_c):
            new_c = {}
            for name, kind in self.layout:
                if kind in ("mlp", "moe"):
                    x, _, _ = _apply_sub(kind, unit_p[name], x, ctx, cfg)
                else:
                    x, new_c[name] = B.CHUNK[kind](unit_p[name], x,
                                                   unit_c[name], ctx, cfg)
            return x, new_c

        if self.n_units:
            def body(x, pc):
                p_u, c_u = pc
                return unit_step(x, p_u, c_u)
            x, new_units = jax.lax.scan(body, x,
                                        (params["units"], cache["units"]))
            cache = dict(cache, units=new_units)
        if self.n_tail:
            new_tail = {}
            for name, kind in self.tail_layout:
                if kind in ("mlp", "moe"):
                    x, _, _ = _apply_sub(kind, params["tail"][name], x, ctx,
                                         cfg)
                else:
                    x, new_tail[name] = B.CHUNK[kind](
                        params["tail"][name], x, cache["tail"][name], ctx,
                        cfg)
            cache = dict(cache, tail=new_tail)
        with jax.named_scope("lm.head"):
            x = B._norm(params["final_norm"], x, cfg.norm_eps)
            nvalid = (batch["segment_ids"] > 0).sum(-1)
            xlast = x[jnp.arange(x.shape[0]), jnp.maximum(nvalid - 1, 0)]
            logits = (xlast @ self._head_t(params).astype(xlast.dtype))
        return logits.astype(jnp.float32), cache, cache_len + nvalid

    def reset_cache_rows(self, cache, fresh):
        """Zero the given cache rows (``fresh`` (B,) bool) back to their
        ``init_cache`` values — the engine calls this when it claims a
        chunk row for a new request, so no stale conv tail / attention ring
        / stabilizer state leaks across tenants. Leaves named ``m`` are
        log-domain stabilizers whose empty value is -1e30, not 0."""
        def one(path, leaf):
            stacked = any(getattr(p, "key", None) == "units" for p in path)
            extra = leaf.ndim - (2 if stacked else 1)
            m = fresh.reshape(((1,) if stacked else ())
                              + fresh.shape + (1,) * extra)
            empty = -1e30 if getattr(path[-1], "key", None) == "m" else 0
            return jnp.where(m, jnp.asarray(empty, leaf.dtype), leaf)

        return jax.tree_util.tree_map_with_path(one, cache)

    def expand_chunk_states(self, cache):
        """View a chunk cache (``init_cache`` layout, (B, …) leaves) as a
        1-segment packed-states tree ((B, 1, …) leaves) so the existing
        ``scatter_into_cache`` / ``prefill_probe`` machinery handles the
        chunk→decode-slot handoff unchanged."""
        def one(path, leaf):
            stacked = any(getattr(p, "key", None) == "units" for p in path)
            if stacked:
                return leaf.reshape(leaf.shape[:2] + (1,) + leaf.shape[2:])
            return leaf.reshape((leaf.shape[0], 1) + leaf.shape[1:])

        return jax.tree_util.tree_map_with_path(one, cache)

    def scatter_into_cache(self, cache, states, src, dst):
        """Land harvested per-segment states in arbitrary decode slots.

        cache: slot-major decode cache (``init_cache`` layout); states: the
        pytree from ``prefill_packed`` ((B, S, …) leading dims); src (M,)
        int32 flat indices into the flattened B·S segment axis; dst (M,)
        int32 target slot rows. Entries with dst outside [0, n_slots) are
        DROPPED (use n_slots as a sentinel), so a fixed M compiles once
        regardless of how many slots a round actually refills.

        Returns the updated cache (jit/donate-friendly: pure function)."""
        def one(path, c, s):
            stacked = any(getattr(p, "key", None) == "units" for p in path)
            if stacked:                     # (n_units, B, S, …) leaves
                flat = s.reshape((s.shape[0], -1) + s.shape[3:])
                return c.at[:, dst].set(flat[:, src].astype(c.dtype),
                                        mode="drop")
            flat = s.reshape((-1,) + s.shape[2:])
            return c.at[dst].set(flat[src].astype(c.dtype), mode="drop")

        return jax.tree_util.tree_map_with_path(one, cache, states)

    # ----------------------------------------------------------- decode
    def init_cache(self, batch_size: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)

        def one_unit(layout):
            c = {}
            for name, kind in layout:
                if kind in ("mlp", "moe"):
                    continue
                if kind == "attn":
                    c[name] = B.init_attn_cache(cfg, batch_size, max_len, dt)
                else:
                    c[name] = B.CACHE_INIT[kind](cfg, batch_size, dt)
            return c

        cache: Dict[str, Any] = {}
        if self.n_units:
            u = one_unit(self.layout)
            cache["units"] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a[None], (self.n_units,) + a.shape).copy(), u)
        if self.n_tail:
            cache["tail"] = one_unit(self.tail_layout)
        return cache

    def decode_step(self, params, cache, tokens_t, cache_len,
                    reset: Optional[jnp.ndarray] = None,
                    mrope_positions: Optional[jnp.ndarray] = None):
        """tokens_t (B, 1) [or frames_t (B,1,d) for audio, unused];
        cache_len (B,) cursor. Returns (logits (B, V), new_cache)."""
        cfg = self.cfg
        x = self._lookup(params, tokens_t)
        ctx = Ctx(cache_len=cache_len, reset_t=reset,
                  mrope_positions=mrope_positions)

        def unit_step(x, unit_p, unit_c):
            new_c = {}
            for name, kind in self.layout:
                if kind in ("mlp", "moe"):
                    x, _, _ = _apply_sub(kind, unit_p[name], x, ctx, cfg)
                else:
                    x, new_c[name] = B.STEP[kind](unit_p[name], x,
                                                  unit_c[name], ctx, cfg)
            return x, new_c

        if self.n_units:
            def body(x, pc):
                p_u, c_u = pc
                x, c_new = unit_step(x, p_u, c_u)
                return x, c_new
            x, new_units = jax.lax.scan(body, x,
                                        (params["units"], cache["units"]))
            cache = dict(cache, units=new_units)
        if self.n_tail:
            new_tail = {}
            for name, kind in self.tail_layout:
                if kind in ("mlp", "moe"):
                    x, _, _ = _apply_sub(kind, params["tail"][name], x, ctx,
                                         cfg)
                else:
                    x, new_tail[name] = B.STEP[kind](
                        params["tail"][name], x, cache["tail"][name], ctx,
                        cfg)
            cache = dict(cache, tail=new_tail)
        x = B._norm(params["final_norm"], x, cfg.norm_eps)
        logits = (x[:, 0] @ self._head_t(params).astype(x.dtype))
        return logits.astype(jnp.float32), cache

    def decode_step_sample(self, params, cache, tokens_t, cache_len, keys,
                           temperature, top_k, top_p,
                           reset: Optional[jnp.ndarray] = None):
        """One fused decode + batched-sampling step over all slots.

        keys (B, 2) uint32 per-slot PRNG carry; temperature/top_k/top_p (B,)
        per-slot sampling knobs (temperature <= 0 → greedy; see
        blocks.sample_from_logits). ONE jitted call per token — the sampled
        token never round-trips to the host between the forward and the
        sample. Returns (tokens (B,) int32, logits (B, V) f32, new_cache,
        new_keys)."""
        logits, cache = self.decode_step(params, cache, tokens_t, cache_len,
                                         reset)
        tok, keys = B.sample_from_logits(logits, keys, temperature, top_k,
                                         top_p)
        return tok, logits, cache, keys

    def decode_step_sample_guarded(self, params, cache, tokens_t, cache_len,
                                   keys, temperature, top_k, top_p, poison,
                                   reset: Optional[jnp.ndarray] = None):
        """``decode_step_sample`` with the serve engine's numerical guard
        rail fused in: a per-slot finiteness probe on the decode logits
        (one (B, V) isfinite + all-reduce — cheap next to the forward) so a
        poisoned slot is caught the step it goes bad instead of silently
        emitting garbage.

        ``poison`` (B,) f32 is the fault-injection seam: it is ADDED to the
        logits before the probe and the sampler. In production it is all
        zeros — ``x + 0.0`` is a bitwise no-op on every finite logit, so
        guarded token streams are bit-identical to unguarded ones — while a
        fault plan puts NaN/Inf there to script a numerical failure the
        probe must catch. Returns (tokens (B,), logits (B, V) f32,
        new_cache, new_keys, finite (B,) bool)."""
        logits, cache = self.decode_step(params, cache, tokens_t, cache_len,
                                         reset)
        logits = logits + poison[:, None]
        finite = jnp.all(jnp.isfinite(logits), axis=-1)
        tok, keys = B.sample_from_logits(logits, keys, temperature, top_k,
                                         top_p)
        return tok, logits, cache, keys, finite

    # ------------------------------------------------- speculative decode
    def decode_verify(self, params, cache, tokens_t, cache_len, draft):
        """Score K draft tokens with K+1 chained greedy decode steps in ONE
        call, keeping the per-step cache trajectory for rollback.

        ``tokens_t`` (B, 1) is each slot's last committed token; ``draft``
        (B, K) the proposed continuations. Step j feeds input j of
        [tokens_t, draft] at position ``cache_len + j`` and takes the
        argmax — exactly what sequential ``decode_step`` + argmax would
        compute, so a draft token is *accepted* iff it matches the argmax
        and the committed stream is bit-identical to non-speculative
        greedy decode by construction.

        Returns (toks (B, K+1) int32 — the argmax after each step,
        finite (B, K+1) bool — per-step logits finiteness for the guard
        rail, traj — cache pytree with a leading (K+1,) axis; entry j is
        the cache after consuming j+1 inputs). ``spec_rollback`` selects
        each row's post-accept cache from ``traj``."""
        inputs = jnp.moveaxis(
            jnp.concatenate([tokens_t, draft.astype(jnp.int32)], axis=1),
            1, 0)                                       # (K+1, B)

        def body(carry, inp):
            c, off = carry
            logits, c = self.decode_step(params, c, inp[:, None],
                                         cache_len + off)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            fin = jnp.all(jnp.isfinite(logits), axis=-1)
            return (c, off + 1), (tok, fin, c)

        (_, _), (toks, fins, traj) = jax.lax.scan(
            body, (cache, jnp.zeros((), jnp.int32)), inputs)
        return jnp.moveaxis(toks, 0, 1), jnp.moveaxis(fins, 0, 1), traj

    def spec_rollback(self, traj, idx):
        """Per-row rollback select over a verify trajectory: row ``b`` of
        the returned decode cache is row ``b`` of ``traj`` entry
        ``idx[b]`` (idx (B,) int32 in [0, K]) — the O(1) state is what
        makes rejecting draft tokens a cheap gather instead of a replay.
        Rows whose idx points at entry 0 simply keep the state after their
        first (always-valid) verify step."""
        def one(path, leaf):
            stacked = any(getattr(p, "key", None) == "units" for p in path)
            if stacked:                     # (K+1, n_units, B, …)
                bsz = leaf.shape[2]
                out = leaf[idx, :, jnp.arange(bsz)]     # (B, n_units, …)
                return jnp.moveaxis(out, 0, 1)
            bsz = leaf.shape[1]             # (K+1, B, …)
            return leaf[idx, jnp.arange(bsz)]

        return jax.tree_util.tree_map_with_path(one, traj)

    def prefill_probe(self, states, logits):
        """Per-segment finiteness of a packed prefill's harvest: True at
        (b, s) iff every state leaf AND the segment-end logits of that
        segment are finite. One all-reduce per leaf over the non-(B, S)
        axes — the admission-path guard rail: a poisoned segment is
        quarantined before its state is ever trusted by a decode slot.
        Absent segments (states zeroed, logits masked to 0) probe True."""
        ok = jnp.all(jnp.isfinite(logits), axis=-1)         # (B, S)

        def leaf_ok(path, a):
            stacked = any(getattr(p, "key", None) == "units" for p in path)
            if stacked:                                     # (n_units,B,S,…)
                axes = (0,) + tuple(range(3, a.ndim))
            else:                                           # (B, S, …)
                axes = tuple(range(2, a.ndim))
            return jnp.all(jnp.isfinite(a), axis=axes)

        for leaf in jax.tree_util.tree_leaves_with_path(states):
            ok = ok & leaf_ok(*leaf)
        return ok

    def sample_tokens(self, logits, keys, temperature, top_k, top_p):
        """Sample one token per row from already-computed logits (the packed
        prefill's (K, V) segment-end logits, flattened). Same per-row knob
        semantics as ``decode_step_sample``; returns (tokens (K,) int32,
        new_keys (K, 2))."""
        return B.sample_from_logits(logits, keys, temperature, top_k, top_p)


def build_model(cfg: ArchConfig) -> LM:
    return LM(cfg)
