"""Training loop: grad accumulation, mixed precision, checkpoint/restart,
SIGTERM-safe emergency save, deterministic data replay, throughput metering.

Distribution notes (the 1000+-node posture, exercised by the dry-run):
  * train_step is built once and jit'ed with in/out shardings from
    distributed/sharding.py — batch over ("pod","data"), params FSDP×TP.
    ``Trainer(mesh=...)`` does this on a live mesh: the state is created
    sharded, each batch is placed by its sharding before the step (the
    ``train.place`` span), and the step's outputs keep the state's
    shardings. The step is traced with the mesh as its abstract mesh, so
    code that cannot be SPMD-partitioned (the Pallas scan, kernels/ops.py)
    runs per shard.
  * gradient accumulation runs as a lax.scan over microbatches with an f32
    (or bf16 — ``grad_accum_dtype``, the memory-compression knob) carried
    accumulator; XLA overlaps the per-microbatch reduce-scatter with the
    next microbatch's backward (latency-hiding scheduler, enabled in
    launch/train.py flags).
  * elastic restart: checkpoints are mesh-agnostic (see checkpoint.py);
    `Trainer.restore()` re-device_puts onto the current mesh.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.optim.adamw import AdamW
from repro.checkpoint.checkpoint import CheckpointManager
from repro.distributed import sharding as shd
from repro.obs import Obs


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    accum: int = 1                       # gradient-accumulation microbatches
    grad_accum_dtype: Optional[str] = None   # "bfloat16" halves accum HBM
    log_every: int = 10
    ckpt_every: int = 0                  # 0 = no periodic checkpoints
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3


def make_train_step(model, opt: AdamW, accum: int = 1,
                    grad_accum_dtype: Optional[str] = None) -> Callable:
    """Returns step_fn(state, batch) -> (state, metrics).

    state = {"params": ..., "opt": AdamWState}; batch leaves have leading
    global-batch dim divisible by ``accum``.
    """

    def loss_fn(params, mb):
        loss, metrics = model.loss(params, mb)
        return loss, metrics

    def step_fn(state, batch):
        params = state["params"]
        if accum <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            adt = jnp.dtype(grad_accum_dtype) if grad_accum_dtype else \
                jnp.float32
            mb = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) +
                                    x.shape[1:]), batch)

            def micro(carry, mbatch):
                gacc, lacc = carry
                (l, met), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mbatch)
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(adt), gacc, g)
                return (gacc, lacc + l), met

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, adt), params)
            (grads, lsum), mets = jax.lax.scan(micro, (g0, 0.0), mb)
            grads = jax.tree.map(lambda g: (g / accum).astype(jnp.float32),
                                 grads)
            loss = lsum / accum
            metrics = jax.tree.map(lambda m: m.mean(), mets)
        new_params, new_opt, stats = opt.update(grads, state["opt"], params)
        metrics = dict(metrics, loss=loss, **stats)
        return {"params": new_params, "opt": new_opt}, metrics

    return step_fn


def _traced_on(mesh, fn: Callable) -> Callable:
    """``fn`` traced with ``mesh`` as the abstract mesh."""
    def traced(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)
    return traced


class Trainer:
    def __init__(self, model, opt: AdamW, loader, cfg: TrainerConfig,
                 step_fn: Optional[Callable] = None, jit: bool = True,
                 obs: Optional[Obs] = None, mesh=None):
        self.model = model
        self.opt = opt
        self.loader = loader
        self.cfg = cfg
        self.mesh = mesh
        fn = step_fn or make_train_step(model, opt, cfg.accum,
                                        cfg.grad_accum_dtype)
        self._init = self.init_state
        self.state_sharding = None
        if mesh is not None:
            state_shape = jax.eval_shape(self.init_state,
                                         jax.random.PRNGKey(0))
            self.state_sharding = shd.named(
                mesh, shd.train_state_pspecs(state_shape, mesh))
            self._init = jax.jit(self.init_state,
                                 out_shardings=self.state_sharding)
            fn = jax.jit(_traced_on(mesh, fn), donate_argnums=(0,),
                         out_shardings=(self.state_sharding,
                                        NamedSharding(mesh, P())))
        elif jit:
            fn = jax.jit(fn, donate_argnums=(0,))
        self.step_fn = fn
        self.ckpt = CheckpointManager(cfg.ckpt_dir, keep=cfg.keep_ckpts) \
            if cfg.ckpt_dir else None
        self._interrupted = False
        # telemetry: the train.* counters are THE cumulative token/step
        # metering (the log line reads deltas of these); span tracing is
        # recording only when the caller passes Obs.on(). Note the fused
        # train step is ONE jit-compiled function — forward-backward and
        # the optimizer update share the "train.step" span (an XLA profile
        # via --profile-dir splits them at operator level).
        self.obs = obs if obs is not None else Obs.off()
        m = self.obs.metrics
        self._c_steps = m.counter("train.steps",
                                  help="optimizer steps completed")
        self._c_real = m.counter("train.real_tokens",
                                 help="non-padding tokens trained on")
        self._c_buf = m.counter("train.buffer_tokens",
                                help="buffer tokens incl. padding")
        self._c_compiles = m.counter(
            "train.compiles", help="distinct batch token-shapes seen "
                                   "(first-call = compile)")
        self._g_data = m.gauge("train.data_ms",
                               help="cumulative ms waiting on the loader")
        self._g_step = m.gauge("train.step_ms",
                               help="cumulative ms in the fused train step")
        self._g_loss = m.gauge("train.loss", help="last logged loss")
        self._shapes_seen = set()

    # ----------------------------------------------------------- lifecycle
    def init_state(self, key) -> Dict[str, Any]:
        params = self.model.init(key)
        return {"params": params, "opt": self.opt.init(params)}

    def _place_batch(self, batch):
        """Put a host batch on the mesh, rows over the data axes (without a
        mesh the jitted step transfers it)."""
        return jax.device_put(
            batch, shd.named(self.mesh, shd.batch_pspecs(batch, self.mesh)))

    def restore_or_init(self, key) -> Tuple[Dict[str, Any], int]:
        state = self._init(key)
        if self.ckpt and self.ckpt.latest_step() is not None:
            step = self.ckpt.latest_step()
            state = self.ckpt.restore(state, shardings=self.state_sharding)
            return state, int(self.ckpt.read_meta(step)["meta"]["step"])
        return state, 0

    def _install_signal_handlers(self):
        def handler(signum, frame):
            self._interrupted = True
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, handler)
            except ValueError:
                pass      # non-main thread (tests)

    # ----------------------------------------------------------- train loop
    def train(self, key, start_step: Optional[int] = None, verbose=True):
        self._install_signal_handlers()
        state, step0 = self.restore_or_init(key)
        if start_step is not None:
            step0 = start_step
        history = []
        tr = self.obs.tracer
        t_last = time.perf_counter()
        # the log line meters real/buffer tokens as DELTAS of the train.*
        # registry counters — one source of numbers shared with the trace
        # snapshot and any Prometheus scrape
        real_mark = self._c_real.value
        buf_mark = self._c_buf.value
        for step in range(step0, self.cfg.steps):
            t0 = time.perf_counter()
            with tr.span("train.data", track="train", step=step):
                batch = self.loader.batch(step)
            t1 = time.perf_counter()
            # first occurrence of a batch token-shape = jit compile on this
            # call (first-call timing shows up as an outsized train.step)
            shape = tuple(batch["tokens"].shape)
            compiled = shape not in self._shapes_seen
            if compiled:
                self._shapes_seen.add(shape)
                self._c_compiles.inc()
            sid = tr.start("train.step", track="train", step=step,
                           compile=compiled)
            # meter from the batch itself, not metrics["tokens"]: a loss fn
            # that omits the metric must not silently report 0 tok/s
            seg = batch.get("segment_ids")
            real = int((seg > 0).sum()) if seg is not None \
                else int(batch["tokens"].size)
            if self.mesh is not None:
                with tr.span("train.place", track="train", step=step):
                    batch = self._place_batch(batch)
            state, metrics = self.step_fn(state, batch)
            # sync so the span covers device time, not dispatch time — a
            # no-op on the disabled tracer (no extra syncs when off)
            tr.sync(metrics["loss"])
            tr.finish(sid)
            t2 = time.perf_counter()
            # the host's own work after each step: counters, the log
            # line, the history row (which reads the metrics back from
            # the device) and the checkpoint test
            with tr.span("train.host", track="train", step=step):
                self._c_steps.inc()
                self._c_real.inc(real)
                self._c_buf.inc(int(batch["tokens"].size))
                self._g_data.add((t1 - t0) * 1e3)
                self._g_step.add((t2 - t1) * 1e3)
                if verbose and (step + 1) % self.cfg.log_every == 0:
                    jax.block_until_ready(metrics["loss"])
                    self._g_loss.set(float(metrics["loss"]))
                    dt = time.perf_counter() - t_last
                    real_since = self._c_real.value - real_mark
                    buffer_since = self._c_buf.value - buf_mark
                    real_tput = real_since / max(dt, 1e-9)
                    buf_tput = buffer_since / max(dt, 1e-9)
                    print(f"step {step + 1:5d} "
                          f"loss {float(metrics['loss']):.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"tok/s {real_tput:,.0f} "
                          f"(buffer {buf_tput:,.0f}, "
                          f"{real_since / max(buffer_since, 1):.0%} real)")
                    t_last = time.perf_counter()
                    real_mark = self._c_real.value
                    buf_mark = self._c_buf.value
                row = {k: float(v) for k, v in metrics.items()
                       if jnp.ndim(v) == 0}
                row["real_tokens"] = float(real)
                row["buffer_tokens"] = float(batch["tokens"].size)
                history.append(row)
                if self.ckpt and self.cfg.ckpt_every and \
                        (step + 1) % self.cfg.ckpt_every == 0:
                    self.ckpt.save(step + 1, state, meta={"step": step + 1})
                if self._interrupted:
                    if self.ckpt:        # emergency checkpoint on SIGTERM
                        self.ckpt.save(step + 1, state,
                                       meta={"step": step + 1,
                                             "emergency": True},
                                       blocking=True)
                    break
        if self.ckpt:
            self.ckpt.wait()
        return state, history
