"""Driver of the ``train`` cells: packed training through the program's
``Trainer`` and its prefetching packing loader, as launch/train.py runs it.

Set-up builds one Trainer, compiles its jitted packed step for the cell's
(rows, seq_len), and drives it from the seed through its first
``check_steps`` steps inside ``Trainer.train``; the same call then runs
the measured window, and the Trainer's own SIGTERM stop ends it once
``--seconds`` have passed. Afterwards the plain reference follows the
first steps on the same documents, each alone, and the run is correct
when the losses, the first gradient (read from the optimizer's first
moment after one step) and the parameters' change over the check steps
agree with it.

End-to-end metric: ``train_tokens_per_s``, real (non-padding) tokens of
every step completed in the window over the window's time, per chip.
"""
from __future__ import annotations

import gc
import signal
import time
from typing import Dict, List, Optional

import numpy as np

from bench.lib import harness as H
from bench.lib.model import (bench_name, check_layout, from_program,
                             init_weights, jax_key, program_config, sizes,
                             to_program)
from bench.lib import xtrace
from bench.lib.traffic import Corpus
from bench.reference import mamba as ref

# A leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone under Adam; its change is not compared.
UNMOVED = 1e-3


def _faulty_step(fault: str, model, opt):
    """Broken packed steps for the harness's own tests and fault readings
    (``control_fp8`` runs the program as it is; see ``_control``)."""
    from repro.train.trainer import make_train_step
    real = make_train_step(model, opt)
    if fault == "control_fp8":
        return None
    if fault == "unchanged_state":
        return lambda state, batch: (state, real(state, batch)[1])
    if fault == "half_batch":
        return lambda state, batch: real(
            state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    raise KeyError(fault)


def build_trainer(conf: dict, traffic: dict, loader, obs=None,
                  fault: Optional[str] = None):
    """The program's Trainer for this configuration and job, its state
    made from the benchmark's weights in one jitted call."""
    import jax
    from repro.models.lm import build_model
    from repro.optim.adamw import AdamW, AdamWConfig, cosine_schedule
    from repro.train.trainer import Trainer, TrainerConfig

    s = sizes(conf)
    model = build_model(program_config(conf))
    o = traffic["optimizer"]
    opt = AdamW(cosine_schedule(o["lr"], warmup=o["warmup"],
                                total=o["total"]),
                AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                            weight_decay=o["weight_decay"],
                            clip_norm=o["clip_norm"]))

    @jax.jit
    def make_state(key):
        params = to_program(init_weights(s, key))
        return {"params": params, "opt": opt.init(params)}

    class BenchTrainer(Trainer):
        def restore_or_init(self, key):
            return make_state(key), 0

    step_fn = _faulty_step(fault, model, opt) if fault else None
    trainer = BenchTrainer(model, opt, loader,
                           TrainerConfig(steps=1 << 40, log_every=1 << 40),
                           step_fn=step_fn, obs=obs)
    trainer.make_state = make_state
    return trainer, model


class _AnnotatedLoader:
    """The loader as the Trainer sees it, each call marked in the profile."""

    def __init__(self, loader):
        self.loader = loader

    def batch(self, step: int):
        import jax
        with jax.profiler.TraceAnnotation("bench.data"):
            return self.loader.batch(step)


def _leaf_norms(tree) -> Dict[str, float]:
    return {bench_name(k): v for k, v in ref.leaf_norms(tree).items()}


class _Steps:
    """The Trainer's step function: the compiled packed step, plus the
    readings of the check steps and the window's clock."""

    def __init__(self, compiled, seconds: float, check_steps: int, b1: float,
                 clock: H.SetupClock, trace_dir, registry):
        self.compiled = compiled
        self.seconds = seconds
        self.check_steps = check_steps
        self.b1 = b1
        self.clock = clock
        self.trace_dir = trace_dir
        self.registry = registry
        self.registry_t0 = None
        self.compiles = H.CompileCounter()
        self.calls = 0
        self.losses: List[float] = []
        self.batches: List[Dict[str, np.ndarray]] = []
        self.grad: Dict[str, float] = {}
        self.params_after = None
        self.window_steps = 0
        self.window_tokens = 0
        self.buffer_tokens = 0
        self.t0 = self.t1 = None
        self._window_ann = None

    def _start_window(self, state):
        import jax
        self.params_after = jax.device_get(state["params"])
        self.clock.mark("check_steps")
        if self.trace_dir is not None:
            xtrace.start(self.trace_dir)
            self._window_ann = jax.profiler.TraceAnnotation("bench.window")
            self._window_ann.__enter__()
        jax.config.update("jax_log_compiles", True)   # names any compile
        # the Trainer counts a step after it returns: this is the window's
        # start, and the registry once train() returns is its end
        self.registry_t0 = self.registry.to_dict()
        self.compiles.on = True
        self.t0 = time.perf_counter()

    def __call__(self, state, batch):
        import jax
        c = self.calls
        if c == 0:
            self.clock.mark("init_weights")
        if c == 1:
            m = state["opt"].m
            self.grad = {k: v / (1 - self.b1)
                         for k, v in _leaf_norms(m).items()}
        if c == self.check_steps:
            self._start_window(state)
        seg = np.asarray(batch["segment_ids"])
        if c < self.check_steps:
            self.batches.append({k: np.asarray(v) for k, v in batch.items()})
        with jax.profiler.TraceAnnotation("bench.train_step"):
            out = self.compiled(state, batch)
            jax.block_until_ready(out)
        self.calls += 1
        if c == 0:
            self.clock.mark("first_step")
        if c < self.check_steps:
            self.losses.append(float(out[1]["loss"]))
            return out
        self.window_steps += 1
        self.window_tokens += int((seg > 0).sum())
        self.buffer_tokens += seg.size
        now = time.perf_counter()
        if now - self.t0 >= self.seconds:
            self.t1 = now
            self.compiles.on = False
            jax.config.update("jax_log_compiles", False)
            if self._window_ann is not None:
                self._window_ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
            signal.raise_signal(signal.SIGTERM)   # Trainer stops after this
        return out


def _docs(batch: Dict[str, np.ndarray]) -> List[np.ndarray]:
    """The documents of a packed batch, row by row."""
    out = []
    for tok, pos, seg in zip(batch["tokens"], batch["positions"],
                             batch["segment_ids"]):
        starts = np.flatnonzero((pos == 0) & (seg > 0))
        for a in starts:
            n = int(np.sum(seg == seg[a]))
            out.append(tok[a:a + n])
    return out


def _unmatched(docs: List[np.ndarray], handed: List[np.ndarray]) -> int:
    """Documents of a batch that are not among those the generator gave."""
    pool: Dict[bytes, int] = {}
    for d in handed:
        pool[d.tobytes()] = pool.get(d.tobytes(), 0) + 1
    bad = 0
    for d in docs:
        k = d.tobytes()
        if pool.get(k, 0) > 0:
            pool[k] -= 1
        else:
            bad += 1
    return bad


def _gap(prog: Dict[str, float], want: Dict[str, float], keys) -> float:
    """Worst leaf: |program norm - reference norm| over the larger of the
    reference leaf's norm and the median leaf's."""
    med = float(np.median([want[k] for k in keys]))
    return max(abs(prog[k] - want[k]) / max(want[k], med) for k in keys)


def compare(prog: dict, want: dict) -> Dict[str, float]:
    """The numbers compared, from the program's readings and the
    reference's (both: losses, grad and change norms by leaf)."""
    keys = sorted(want["grad"])
    med = float(np.median([want["grad_raw"][k] for k in keys]))
    moved = [k for k in keys if want["grad_raw"][k] >= UNMOVED * med]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                   want["losses"])),
        "grad_gap": _gap(prog["grad"], want["grad"], keys),
        "change_gap": _gap(prog["change"], want["change"], moved),
    }


def reference_readings(conf: dict, traffic: dict, w, docs_per_step,
                       prec: str = "f32") -> dict:
    s = sizes(conf)
    return ref.train(w, docs_per_step, traffic["optimizer"], s.eps,
                     prec=prec,
                     max_tokens=ref.block_tokens(s.layers, s.d, s.di))


def run(cell: H.Cell, seed: int, seconds: float, trace: bool,
        clock: H.SetupClock, devs, fault: Optional[str] = None) -> H.Result:
    import jax
    import jax.numpy as jnp
    from repro.data.packing_loader import LoaderConfig, PackingLoader
    from repro.data.prefetch import PrefetchLoader
    from repro.obs import Obs

    t, conf = cell.traffic, cell.config
    s = sizes(conf)
    rows, seq_len, n_check = t["rows"], t["seq_len"], t["check_steps"]
    corpus = Corpus(t["documents"], seed, s.vocab_real)
    obs = Obs.on() if trace else Obs.off()
    prefetch = PrefetchLoader(
        PackingLoader(corpus, LoaderConfig(rows=rows, seq_len=seq_len)),
        depth=t["prefetch"], obs=obs)
    trainer, model = build_trainer(conf, t, _AnnotatedLoader(prefetch),
                                   obs=obs, fault=fault)
    check_layout(model, s)
    key = jax_key(seed, 1)
    clock.mark("build")

    state_shape = jax.eval_shape(trainer.make_state, key)
    batch_shape = {k: jax.ShapeDtypeStruct((rows, seq_len), jnp.int32)
                   for k in ("tokens", "positions", "segment_ids")}
    compiled = trainer.step_fn.lower(state_shape, batch_shape).compile()
    clock.mark("compile_or_cache")

    trace_dir = None
    if trace:
        trace_dir = cell.out_dir / f"trace-{seed}"
        trace_dir.mkdir(parents=True, exist_ok=True)
    steps = _Steps(compiled, seconds, n_check, t["optimizer"]["b1"], clock,
                   trace_dir, obs.metrics)
    trainer.step_fn = steps
    handlers = {sig: signal.getsignal(sig)
                for sig in (signal.SIGTERM, signal.SIGINT)}
    try:
        state, _ = trainer.train(key, verbose=False)
    finally:
        for sig, h in handlers.items():    # Trainer.train installs its own
            signal.signal(sig, h)
        prefetch.close()
    window_s = steps.t1 - steps.t0
    setup_s = clock.total(steps.t0)
    device = H.device_info(devs)
    H.note(f"window: {steps.window_steps} steps, {steps.window_tokens} real "
           f"of {steps.buffer_tokens} tokens in {window_s:.4f} s")

    H.note(f"{steps.compiles.n} compilations in the window")
    layer_data = H.window_data(steps.t0, steps.t1, devs, conf, obs,
                               steps.registry_t0, obs.metrics.to_dict())

    # free the program's state before the reference runs; keep the jitted
    # program that made it, to make the same initial weights again
    make_state = trainer.make_state
    del state, compiled, trainer
    steps.compiled = None
    gc.collect()

    docs = [_docs(b) for b in steps.batches]
    unmatched = sum(_unmatched(d, corpus.handed_out[i])
                    for i, d in enumerate(docs))
    w0 = from_program(make_state(key)["params"])
    after = jax.tree_util.tree_flatten_with_path(steps.params_after)[0]
    change = {bench_name(jax.tree_util.keystr(p)): float(
        jnp.linalg.norm((jnp.asarray(a) - b).ravel()))
        for (p, a), b in zip(after, jax.tree.leaves(to_program(w0)))}
    prog = {"losses": steps.losses, "grad": steps.grad, "change": change}
    t_ref = time.perf_counter()
    want = reference_readings(conf, t, w0, docs)
    H.note(f"reference: {len(docs)} steps, "
           f"{sum(len(d) for d in docs)} documents, "
           f"{time.perf_counter() - t_ref:.1f} s")
    H.note("losses program " + " ".join(f"{x:.6f}" for x in prog["losses"])
           + " reference " + " ".join(f"{x:.6f}" for x in want["losses"]))
    nums = compare(prog, want)
    if fault == "control_fp8":
        nums = _control(conf, t, make_state, key, steps.batches, want, nums)
    lim = t["limits"]
    checks = [H.Check(k, v, lim[k]) for k, v in nums.items()]
    checks.append(H.Check("docs_not_generated", float(unmatched), 0.0))
    return H.Result(attempted=steps.window_steps, failed=0,
                    end_to_end={"train_tokens_per_s":
                                steps.window_tokens / window_s / len(devs),
                                "setup_s": setup_s},
                    checks=checks, device=device, layer_data=layer_data,
                    trace_dir=trace_dir,
                    compiles_in_window=steps.compiles.n)



def _control(conf, traffic, make_state, key, batches, want, program_nums):
    """The control and a fault read in the reference put in the program's
    place: the reference with float8 matmul operands, and the reference
    trained on the first half of each batch's rows. Notes all three
    readings; returns the control's numbers, which have to fail."""
    from bench.lib.model import from_program
    rows = batches[0]["tokens"].shape[0]
    readings = {"program": program_nums}
    for name, prec, keep in (("control_fp8", "fp8", rows),
                             ("half_batch", "f32", rows // 2)):
        docs = [_docs({k: v[:keep] for k, v in b.items()}) for b in batches]
        w0 = from_program(make_state(key)["params"])
        readings[name] = compare(
            reference_readings(conf, traffic, w0, docs, prec=prec), want)
    for name, nums in readings.items():
        H.note(f"reading {name}: " + " ".join(
            f"{k}={v!r}" for k, v in nums.items()))
    return readings["control_fp8"]
