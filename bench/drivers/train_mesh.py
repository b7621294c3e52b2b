"""Driver of the ``train_mesh`` cells: packed training of a whole model
sharded over a mesh of chips, through the program's ``Trainer(mesh=...)``
and its prefetching packing loader.

The cell's file names the mesh (``{"data": 2, "model": 2}``); the run's
chips are laid out on it in order. The Trainer makes its state sharded by
the program's rules (FSDP over "data", tensor-parallel over "model"), puts
each batch on the mesh before its step, and runs the step as one program
over all the chips. The state is made from the benchmark's seeded weights
(bench/lib/model.py; a tied head is the embedding, so ``lm_head`` is not
made), the step is compiled ahead of time for sharded shapes, and
everything else is bench/drivers/train.py's: the same annotations, check
steps, window, faults and compared numbers. The plain reference
(bench/reference/mamba_tied.py) follows the check steps on the same
documents with its weights and optimizer state placed on the same mesh.

End-to-end metric: ``train_tokens_per_s``, real (non-padding) tokens of
every step completed in the window over the window's time, per chip.
"""
from __future__ import annotations

import dataclasses
import gc
import signal
import time
from typing import Optional

import numpy as np

from bench.drivers import train as T
from bench.lib import harness as H
from bench.lib.model import (bench_name, from_program, init_weights,
                             jax_key, sizes, to_program)
from bench.lib.traffic import Corpus
from bench.reference import mamba as ref
from bench.reference import mamba_tied


def program_config(conf: dict):
    """The program's ArchConfig for this file: its registry entry with the
    file's sizes, its head tied or not as the file says. Execution options
    stay at the program's defaults."""
    from repro.configs.base import get_config
    if conf["family"] != "mamba":
        raise ValueError(f"{conf['name']}: only mamba configurations are "
                         f"supported by this harness")
    s = sizes(conf)
    return dataclasses.replace(
        get_config(conf["registry"]), n_layers=s.layers, d_model=s.d,
        vocab=s.vocab, d_state=s.N, d_conv=s.K,
        expand=conf["ssm_cfg"]["expand"], dt_rank=s.R, norm_eps=s.eps,
        tie_embeddings=conf["tie_embeddings"])


def weights(s, key, tied: bool):
    """bench/lib/model.py's weights, without ``lm_head`` when tied."""
    w = init_weights(s, key)
    if tied:
        del w["lm_head"]
    return w


def program_params(w):
    """``to_program`` for weights with or without ``lm_head``."""
    p = to_program(dict(w, lm_head=w.get("lm_head")))
    if "lm_head" not in w:
        del p["head"]
    return p


def bench_weights(p):
    """``from_program`` for parameters with or without a head."""
    w = from_program(dict(p, head=p.get("head")))
    if "head" not in p:
        del w["lm_head"]
    return w


def check_program(model, s, tied: bool) -> None:
    """Raise unless the program's parameters are exactly the benchmark's
    weights, and unless it embeds a token as its embedding row: published
    Mamba scales neither input nor tied head."""
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(0)
    want = jax.eval_shape(model.init, key)
    got = jax.eval_shape(lambda k: program_params(weights(s, k, tied)), key)
    shapes = lambda t: {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x
                        in jax.tree_util.tree_flatten_with_path(t)[0]}
    if shapes(want) != shapes(got):
        raise RuntimeError(f"program parameter layout differs from the "
                           f"benchmark's weights: program {shapes(want)}, "
                           f"benchmark {shapes(got)}")
    embed = jnp.arange(8 * s.d, dtype=jnp.float32).reshape(8, s.d) / s.d
    tokens = jnp.array([[3, 1, 7]], jnp.int32)
    x = model._embed({"embed": embed}, {"tokens": tokens})
    if not bool(jnp.all(x == embed[tokens].astype(x.dtype))):
        raise RuntimeError("the program's embedding is not its table's rows "
                           "(a scaled input); published Mamba does not "
                           "scale it")


def make_mesh(devs, axes: dict):
    from jax.sharding import Mesh
    return Mesh(np.array(devs).reshape(tuple(axes.values())),
                tuple(axes.keys()))


def build_trainer(conf: dict, traffic: dict, loader, mesh, obs=None,
                  fault: Optional[str] = None):
    """The program's Trainer on ``mesh`` for this configuration and job,
    its state made sharded from the benchmark's weights in one jitted
    call."""
    import jax
    from repro.models.lm import build_model
    from repro.optim.adamw import AdamW, AdamWConfig, cosine_schedule
    from repro.train.trainer import Trainer, TrainerConfig

    s = sizes(conf)
    tied = conf["tie_embeddings"]
    model = build_model(program_config(conf))
    o = traffic["optimizer"]
    opt = AdamW(cosine_schedule(o["lr"], warmup=o["warmup"],
                                total=o["total"]),
                AdamWConfig(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                            weight_decay=o["weight_decay"],
                            clip_norm=o["clip_norm"]))

    class BenchTrainer(Trainer):
        def restore_or_init(self, key):
            return make_state(key), 0

    step_fn = T._faulty_step(fault, model, opt) if fault else None
    trainer = BenchTrainer(model, opt, loader,
                           TrainerConfig(steps=1 << 40, log_every=1 << 40),
                           step_fn=step_fn, obs=obs, mesh=mesh)

    def _state(key):
        params = program_params(weights(s, key, tied))
        return {"params": params, "opt": opt.init(params)}

    make_state = jax.jit(_state, out_shardings=trainer.state_sharding)
    trainer.make_state = make_state
    return trainer, model


def reference_readings(conf: dict, traffic: dict, w, docs_per_step, mesh,
                       prec: str = "f32") -> dict:
    s = sizes(conf)
    chips = mesh.devices.size
    return mamba_tied.train(
        w, docs_per_step, traffic["optimizer"], s.eps, mesh, prec=prec,
        max_tokens=chips * ref.block_tokens(s.layers, s.d, s.di))


def _sharded(tree, shardings):
    import jax
    return jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sh), tree, shardings)


def run(cell: H.Cell, seed: int, seconds: float, trace: bool,
        clock: H.SetupClock, devs, fault: Optional[str] = None) -> H.Result:
    import jax
    import jax.numpy as jnp
    from repro.data.packing_loader import LoaderConfig, PackingLoader
    from repro.data.prefetch import PrefetchLoader
    from repro.distributed import sharding as shd
    from repro.obs import Obs

    t, conf = cell.traffic, cell.config
    s = sizes(conf)
    rows, seq_len, n_check = t["rows"], t["seq_len"], t["check_steps"]
    mesh = make_mesh(devs, t["mesh"])
    corpus = Corpus(t["documents"], seed, s.vocab_real)
    obs = Obs.on() if trace else Obs.off()
    prefetch = PrefetchLoader(
        PackingLoader(corpus, LoaderConfig(rows=rows, seq_len=seq_len)),
        depth=t["prefetch"], obs=obs)
    trainer, model = build_trainer(conf, t, T._AnnotatedLoader(prefetch),
                                   mesh, obs=obs, fault=fault)
    check_program(model, s, conf["tie_embeddings"])
    key = jax_key(seed, 1)
    clock.mark("build")

    state_shape = _sharded(jax.eval_shape(trainer.make_state, key),
                           trainer.state_sharding)
    batch = {k: jax.ShapeDtypeStruct((rows, seq_len), jnp.int32)
             for k in ("tokens", "positions", "segment_ids")}
    batch_shape = _sharded(batch, shd.named(mesh,
                                            shd.batch_pspecs(batch, mesh)))
    compiled = trainer.step_fn.lower(state_shape, batch_shape).compile()
    clock.mark("compile_or_cache")

    trace_dir = None
    if trace:
        trace_dir = cell.out_dir / f"trace-{seed}"
        trace_dir.mkdir(parents=True, exist_ok=True)
    steps = T._Steps(compiled, seconds, n_check, t["optimizer"]["b1"], clock,
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
           f"of {steps.buffer_tokens} tokens in {window_s:.4f} s on "
           f"mesh {dict(mesh.shape)}")
    H.note(f"{steps.compiles.n} compilations in the window")
    layer_data = H.window_data(steps.t0, steps.t1, devs, conf, obs,
                               steps.registry_t0, obs.metrics.to_dict())

    # free the program's state before the reference runs; keep the jitted
    # program that made it, to make the same initial weights again
    make_state = trainer.make_state
    del state, compiled, trainer
    steps.compiled = None
    gc.collect()

    docs = [T._docs(b) for b in steps.batches]
    unmatched = sum(T._unmatched(d, corpus.handed_out[i])
                    for i, d in enumerate(docs))
    w0 = bench_weights(make_state(key)["params"])
    after = jax.tree_util.tree_flatten_with_path(steps.params_after)[0]
    change = {bench_name(jax.tree_util.keystr(p)): float(
        jnp.linalg.norm((jax.device_put(a, b.sharding) - b).ravel()))
        for (p, a), b in zip(after, jax.tree.leaves(program_params(w0)))}
    prog = {"losses": steps.losses, "grad": steps.grad, "change": change}
    t_ref = time.perf_counter()
    want = reference_readings(conf, t, w0, docs, mesh)
    H.note(f"reference: {len(docs)} steps, "
           f"{sum(len(d) for d in docs)} documents, "
           f"{time.perf_counter() - t_ref:.1f} s")
    H.note("losses program " + " ".join(f"{x:.6f}" for x in prog["losses"])
           + " reference " + " ".join(f"{x:.6f}" for x in want["losses"]))
    nums = T.compare(prog, want)
    if fault == "control_fp8":
        nums = _control(conf, t, make_state, key, steps.batches, want, nums,
                        mesh)
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


def _control(conf, traffic, make_state, key, batches, want, program_nums,
             mesh):
    """bench/drivers/train.py's ``_control`` with the tied reference on
    the mesh: the reference with float8 matmul operands, and the reference
    trained on the first half of each batch's rows. Notes all three
    readings; returns the control's numbers, which have to fail."""
    rows = batches[0]["tokens"].shape[0]
    readings = {"program": program_nums}
    for name, prec, keep in (("control_fp8", "fp8", rows),
                             ("half_batch", "f32", rows // 2)):
        docs = [T._docs({k: v[:keep] for k, v in b.items()}) for b in batches]
        w0 = bench_weights(make_state(key)["params"])
        readings[name] = T.compare(
            reference_readings(conf, traffic, w0, docs, mesh, prec=prec),
            want)
    for name, nums in readings.items():
        H.note(f"reading {name}: " + " ".join(
            f"{k}={v!r}" for k, v in nums.items()))
    return readings["control_fp8"]
