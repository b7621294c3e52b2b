"""AdamW with decoupled weight decay, global-norm clipping and LR schedules.

Built from scratch (no optax in the environment). State is a pytree pair
(m, v) mirroring params, kept in f32 regardless of param dtype, plus a
scalar step. Weight decay applies only to rank≥2 weights (norm scales,
biases, per-channel gains like Mamba's D are excluded), the standard LLM
recipe.

Mixed precision: when any param leaf is low-precision (bf16/f16), ``init``
also stores an f32 **master** copy. ``update`` then accumulates into the
master and re-rounds to the param dtype each step, so tiny updates are
never lost to bf16's 8-bit mantissa. For all-f32 params ``master`` is
None and the state tree is unchanged from earlier revisions (checkpoints
stay compatible).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jnp.ndarray     # () int32
    m: Any                # pytree like params (f32)
    v: Any                # pytree like params (f32)
    master: Any = None    # f32 param copy when params are low-precision


def _needs_master(params) -> bool:
    return any(jnp.issubdtype(x.dtype, jnp.floating)
               and jnp.dtype(x.dtype).itemsize < 4
               for x in jax.tree.leaves(params))


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0


def cosine_schedule(base_lr: float, warmup: int, total: int,
                    final_frac: float = 0.1) -> Callable:
    def lr(step):
        step = step.astype(jnp.float32) if hasattr(step, "astype") else \
            jnp.asarray(step, jnp.float32)
        warm = base_lr * jnp.minimum(step / jnp.maximum(warmup, 1), 1.0)
        t = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1), 0, 1)
        cos = final_frac + (1 - final_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t))
        return jnp.where(step < warmup, warm, base_lr * cos)
    return lr


def constant_schedule(base_lr: float) -> Callable:
    return lambda step: jnp.asarray(base_lr, jnp.float32)


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(tree, max_norm):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale), tree), norm


class AdamW:
    def __init__(self, lr_fn: Callable, cfg: AdamWConfig = AdamWConfig()):
        self.lr_fn = lr_fn
        self.cfg = cfg

    def init(self, params) -> AdamWState:
        zeros = lambda p: jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), p)
        master = (jax.tree.map(lambda x: x.astype(jnp.float32), params)
                  if _needs_master(params) else None)
        return AdamWState(step=jnp.zeros((), jnp.int32),
                          m=zeros(params), v=zeros(params), master=master)

    @jax.named_scope("optim.adamw")
    def update(self, grads, state: AdamWState, params
               ) -> Tuple[Any, AdamWState, Dict[str, jnp.ndarray]]:
        c = self.cfg
        if c.clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, c.clip_norm)
        else:
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            gnorm = global_norm(grads)
        step = state.step + 1
        lr = self.lr_fn(step)
        b1, b2 = c.b1, c.b2
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        # mixed precision: step from the f32 master (when kept), so bf16
        # rounding never swallows a small update; weight decay also reads
        # the master, not the rounded copy
        masters = state.master if state.master is not None else params

        def upd(g, m, v, p, w):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            mh = m / bc1
            vh = v / bc2
            delta = mh / (jnp.sqrt(vh) + c.eps)
            w32 = w.astype(jnp.float32)
            if c.weight_decay and p.ndim >= 2:
                delta = delta + c.weight_decay * w32
            new_w = w32 - lr * delta
            return new_w.astype(p.dtype), m, v, new_w

        out = jax.tree.map(upd, grads, state.m, state.v, params, masters)
        pick = lambda i: jax.tree.map(
            lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
        new_params, new_m, new_v = pick(0), pick(1), pick(2)
        new_master = pick(3) if state.master is not None else None
        stats = {"grad_norm": gnorm, "lr": lr}
        return new_params, AdamWState(step, new_m, new_v, new_master), stats
