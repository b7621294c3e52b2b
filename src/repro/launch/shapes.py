"""Assigned input shapes × architectures: ShapeDtypeStruct stand-ins,
sharding specs, and jit-able step functions for every dry-run cell.

Shapes (per assignment):
  train_4k     seq 4,096   global_batch 256   → train_step
  prefill_32k  seq 32,768  global_batch 32    → prefill (packed fwd → logits)
  decode_32k   seq 32,768  global_batch 128   → serve_step (1 token, KV cache)
  long_500k    seq 524,288 global_batch 1     → serve_step (sub-quadratic only)

Skip rules (DESIGN.md §4): encoder-only archs have no decode; long_500k
runs only for sub-quadratic archs (SSM/hybrid/windowed attention).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.lm import build_model
from repro.optim.adamw import AdamW, constant_schedule
from repro.train.trainer import make_train_step
from repro.distributed import sharding as shd

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

N_VISION_TOKENS = 256      # vlm stub: patch embeddings per packed buffer


def cell_supported(cfg: ArchConfig, shape_name: str) -> Tuple[bool, str]:
    s = SHAPES[shape_name]
    if s["kind"] == "decode":
        if cfg.encoder_only:
            return False, "encoder-only: no autoregressive step"
        if shape_name == "long_500k" and not cfg.sub_quadratic:
            return False, "full attention: 500k decode is quadratic-regime"
    return True, ""


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs — never allocated)
# ---------------------------------------------------------------------------

def train_batch_specs(cfg: ArchConfig, batch: int, seq: int) -> Dict[str, Any]:
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    bspec: Dict[str, Any] = {
        "tokens": i32((batch, seq)),
        "positions": i32((batch, seq)),
        "segment_ids": i32((batch, seq)),
    }
    if cfg.family == "audio":
        bspec["frames"] = jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                               jnp.dtype(cfg.dtype))
        bspec["labels"] = i32((batch, seq))
    if cfg.family == "vlm":
        bspec["mrope_positions"] = i32((batch, seq, len(cfg.mrope_sections)))
        bspec["vision_embeds"] = jax.ShapeDtypeStruct(
            (batch, N_VISION_TOKENS, cfg.d_model), jnp.dtype(cfg.dtype))
        bspec["vision_positions"] = i32((batch, N_VISION_TOKENS))
    return bspec


def input_specs(cfg: ArchConfig, shape_name: str) -> Dict[str, Any]:
    """Public entry: ShapeDtypeStruct stand-ins for every model input of the
    given cell (weak-type-correct, shardable, no device allocation)."""
    s = SHAPES[shape_name]
    if s["kind"] in ("train", "prefill"):
        return train_batch_specs(cfg, s["batch"], s["seq"])
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(s["batch"], s["seq"]))
    return {
        "cache": cache,
        "tokens_t": jax.ShapeDtypeStruct((s["batch"], 1), jnp.int32),
        "cache_len": jax.ShapeDtypeStruct((s["batch"],), jnp.int32),
    }


# ---------------------------------------------------------------------------
# cell builders: (fn, example_args, in_shardings, out_shardings)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    fn: Any
    args: Tuple
    in_shardings: Tuple
    out_shardings: Any
    meta: Dict[str, Any]


def _resolve_act_pspec(cfg: ArchConfig, mesh, batch: int) -> ArchConfig:
    """act_pspec=("auto",) → sequence-shard the residual carry over 'model'
    (Megatron-SP; right for attention-only stacks). ("auto_d",) → shard the
    d_model dim instead (right for recurrent stacks whose scans are
    channel-parallel but sequential in L)."""
    if cfg.act_pspec == ("auto",):
        cfg = dataclasses.replace(
            cfg, act_pspec=(shd.batch_axis(mesh, batch), "model", None))
    elif cfg.act_pspec == ("auto_d",):
        dspec = shd._fit(mesh, cfg.d_model, "model")
        cfg = dataclasses.replace(
            cfg, act_pspec=(shd.batch_axis(mesh, batch), None, dspec))
    return cfg


def build_train_cell(cfg: ArchConfig, mesh, shape_name: str = "train_4k",
                     accum: int = 1,
                     opt: Optional[AdamW] = None) -> Cell:
    s = SHAPES[shape_name]
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    cfg = _resolve_act_pspec(cfg, mesh, s["batch"])
    model = build_model(cfg)
    opt = opt or AdamW(constant_schedule(1e-4))
    step_fn = make_train_step(model, opt, accum=accum)

    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(opt.init, params_shape)
    state_shape = {"params": params_shape, "opt": opt_shape}
    state_spec = shd.train_state_pspecs(state_shape, mesh)
    batch_shape = train_batch_specs(cfg, s["batch"], s["seq"])
    batch_spec = shd.batch_pspecs(batch_shape, mesh)
    metrics_spec = jax.tree.map(
        lambda _: P(), jax.eval_shape(step_fn, state_shape, batch_shape)[1])
    return Cell(
        fn=step_fn,
        args=(state_shape, batch_shape),
        in_shardings=(shd.named(mesh, state_spec),
                      shd.named(mesh, batch_spec)),
        out_shardings=(shd.named(mesh, state_spec),
                       shd.named(mesh, metrics_spec)),
        meta={"kind": "train", "batch": s["batch"], "seq": s["seq"],
              "fn_name": "train_step"},
    )


def build_prefill_cell(cfg: ArchConfig, mesh,
                       shape_name: str = "prefill_32k") -> Cell:
    s = SHAPES[shape_name]
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    cfg = _resolve_act_pspec(cfg, mesh, s["batch"])
    model = build_model(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # serving: bf16 weights
    params_shape = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16)
        if l.dtype == jnp.float32 else l, params_shape)
    pspec = shd.param_pspecs(params_shape, mesh)
    batch_shape = train_batch_specs(cfg, s["batch"], s["seq"])
    batch_spec = shd.batch_pspecs(batch_shape, mesh)

    def prefill(params, batch):
        return model.prefill_logits(params, batch)

    vshard = shd._fit(mesh, cfg.vocab, "model")
    return Cell(
        fn=prefill,
        args=(params_shape, batch_shape),
        in_shardings=(shd.named(mesh, pspec), shd.named(mesh, batch_spec)),
        out_shardings=NamedSharding(
            mesh, P(shd.batch_axis(mesh, s["batch"]), vshard)),
        meta={"kind": "prefill", "batch": s["batch"], "seq": s["seq"],
              "fn_name": "prefill"},
    )


def build_decode_cell(cfg: ArchConfig, mesh, shape_name: str) -> Cell:
    s = SHAPES[shape_name]
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    model = build_model(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_shape = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16)
        if l.dtype == jnp.float32 else l, params_shape)
    pspec = shd.param_pspecs(params_shape, mesh)
    ins = input_specs(cfg, shape_name)
    cache_spec = shd.cache_pspecs(ins["cache"], mesh, s["batch"])
    b = shd.batch_axis(mesh, s["batch"])

    def serve_step(params, cache, tokens_t, cache_len):
        return model.decode_step(params, cache, tokens_t, cache_len)

    vshard = shd._fit(mesh, cfg.vocab, "model")
    return Cell(
        fn=serve_step,
        args=(params_shape, ins["cache"], ins["tokens_t"], ins["cache_len"]),
        in_shardings=(shd.named(mesh, pspec), shd.named(mesh, cache_spec),
                      NamedSharding(mesh, P(b, None)),
                      NamedSharding(mesh, P(b))),
        out_shardings=(NamedSharding(mesh, P(b, vshard)),
                       shd.named(mesh, cache_spec)),
        meta={"kind": "decode", "batch": s["batch"], "seq": s["seq"],
              "fn_name": "serve_step"},
    )


def build_cell(cfg: ArchConfig, mesh, shape_name: str, **kw) -> Cell:
    kind = SHAPES[shape_name]["kind"]
    if kind == "train":
        return build_train_cell(cfg, mesh, shape_name, **kw)
    if kind == "prefill":
        return build_prefill_cell(cfg, mesh, shape_name)
    return build_decode_cell(cfg, mesh, shape_name)
