"""Bring-up smoke run: packed Mamba training and serving on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded train step

One chip, at the published widths of mamba-110m (16 layers, d_model 1024,
vocab 50280), with random weights and a synthetic corpus made from seeds:

  kernels  conv1d_pack and selective_scan (the per-step walk kernel the
           train cells run) against the sequential oracles of
           kernels/ref.py, outputs and gradients, on a packed slab with
           resets;
  train    five packed steps at seq_len 4096 in bf16 through Trainer and
           make_train_step, once on the XLA scan and conv
           (use_pallas=False) and once on the Pallas kernels
           (use_pallas=True), on the same seeded batches; the step-1
           losses agree;
  serve    a ServeEngine answers 16 requests with several packed prefills
           and mid-flight refills; every request gets its whole token
           budget, and two requests' greedy tokens equal a per-request
           model.prefill + decode_step loop.

--chips 4 runs only the packed train step on a ("data", "model") = (2, 2)
mesh (params and optimizer state sharded by distributed/sharding.py; the
default scan kernel runs per shard) and the one-chip step on the same
global batch; their losses agree and the sharded program holds collectives.

The readings printed on the way (compile seconds, warm step time, tokens
per step, peak device bytes) are smoke readings of one short run, not
benchmark numbers. Without a TPU the script exits nonzero before any
phase; any failed check raises. The last line of a passing run is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

SEQ_LEN = 4096
TRAIN_STEPS = 5
ROW_CANDIDATES = (8, 4, 2)
HEADROOM = 0.85          # a step program may plan for this share of HBM
# bf16 activations (8-bit mantissa) with f32 scan carries and an f32 loss
# reduction: two scan implementations, or one step split over a mesh, round
# differently, but the loss is a mean over ~10^4 tokens near ln(50280)=10.8,
# so rounding noise averages out far below this bound.
LOSS_ATOL = 2e-2
# f32 kernels vs f32 sequential oracles (max error over max magnitude):
# the kernel walks the same recurrence per step, but its backward sums the
# B and C adjoints over channels in a different order (per 32-row group,
# one on the MXU at HIGHEST); both stay at f32 rounding, ~1e-6 relative.
KERNEL_FWD_TOL = 1e-4
KERNEL_GRAD_TOL = 1e-3


def reading(msg: str):
    print(f"[smoke reading] {msg}", flush=True)


def check(cond: bool, msg: str):
    if not cond:
        raise AssertionError(msg)
    print(f"[check] {msg}", flush=True)


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# kernels vs reference
# ---------------------------------------------------------------------------

def packed_positions(rng, Bz: int, L: int) -> np.ndarray:
    """Segments of random length per row; row 0 also restarts exactly on
    the first 256-row kernel chunk boundary."""
    pos = np.zeros((Bz, L), np.int32)
    for b in range(Bz):
        cuts = set(rng.choice(np.arange(1, L), size=5, replace=False))
        if b == 0:
            cuts.add(256)
        start = 0
        for c in sorted(cuts) + [L]:
            pos[b, start:c] = np.arange(c - start)
            start = c
    return pos


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def phase_kernels(cfg, dev):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    Dm, N = cfg.d_inner, cfg.d_state
    f32 = jnp.float32
    t0 = time.perf_counter()

    # selective scan: 4 kernel chunks per row
    Bz, L = 2, 1024
    pos = jnp.asarray(packed_positions(rng, Bz, L))
    u = jnp.asarray(rng.normal(size=(Bz, L, Dm)), f32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (Bz, L, Dm)), f32)
    A = -jnp.exp(jnp.asarray(rng.normal(size=(Dm, N)), f32))
    Bm = jnp.asarray(rng.normal(size=(Bz, L, N)), f32)
    Cm = jnp.asarray(rng.normal(size=(Bz, L, N)), f32)
    D = jnp.asarray(rng.normal(size=(Dm,)), f32)
    cot = jnp.asarray(rng.normal(size=(Bz, L, Dm)), f32)

    def vjp(f, *args):
        y, pull = jax.vjp(f, *args)
        return y, pull(cot[:, :y.shape[1]])

    scan_k = jax.jit(lambda *a: vjp(lambda *x: ops.selective_scan(
        *x, pos, backend="pallas"), *a))
    scan_r = jax.jit(lambda *a: vjp(
        lambda *x: ref.selective_scan_ref(*x, pos), *a))
    args = (u, dt, A, Bm, Cm, D)
    yk, gk = scan_k(*args)
    with jax.default_matmul_precision("highest"):
        yr, gr = scan_r(*args)
    e = rel_err(yk, yr)
    check(e <= KERNEL_FWD_TOL, f"selective_scan fwd vs ref: "
          f"rel err {e:.2e} <= {KERNEL_FWD_TOL}")
    for name, a, b in zip(("u", "delta", "A", "B", "C", "D"), gk, gr):
        e = rel_err(a, b)
        check(e <= KERNEL_GRAD_TOL, f"selective_scan d{name} vs "
              f"ref: rel err {e:.2e} <= {KERNEL_GRAD_TOL}")

    # conv1d_pack: 2 kernel chunks per row, halo across the boundary
    L = 512
    posc = jnp.asarray(packed_positions(rng, Bz, L))
    x = jnp.asarray(rng.normal(size=(Bz, L, Dm)), f32)
    w = jnp.asarray(rng.normal(size=(cfg.d_conv, Dm)), f32)
    b = jnp.asarray(rng.normal(size=(Dm,)), f32)
    conv_k = jax.jit(lambda *a: vjp(lambda *x_: ops.conv1d_pack(
        *x_, posc, backend="pallas"), *a))
    conv_r = jax.jit(lambda *a: vjp(
        lambda *x_: ref.conv1d_pack_ref(*x_, posc), *a))
    yk, gk = conv_k(x, w, b)
    with jax.default_matmul_precision("highest"):
        yr, gr = conv_r(x, w, b)
    e = rel_err(yk, yr)
    check(e <= KERNEL_FWD_TOL, f"conv1d_pack fwd vs ref: rel err {e:.2e} "
          f"<= {KERNEL_FWD_TOL}")
    for name, a, b_ in zip(("x", "weight", "bias"), gk, gr):
        e = rel_err(a, b_)
        check(e <= KERNEL_GRAD_TOL, f"conv1d_pack d{name} vs ref: rel err "
              f"{e:.2e} <= {KERNEL_GRAD_TOL}")
    reading(f"kernels phase {time.perf_counter() - t0:.1f}s incl. compile; "
            f"peak_bytes_in_use {peak_bytes(dev)}")


# ---------------------------------------------------------------------------
# packed training
# ---------------------------------------------------------------------------

def make_trainer(cfg, rows: int, mesh=None):
    from repro.data.dataset import CorpusConfig, SyntheticCorpus
    from repro.data.packing_loader import LoaderConfig, PackingLoader
    from repro.models.lm import build_model
    from repro.optim.adamw import AdamW, AdamWConfig, cosine_schedule
    from repro.train.trainer import Trainer, TrainerConfig

    model = build_model(cfg)
    opt = AdamW(cosine_schedule(3e-4, warmup=1, total=TRAIN_STEPS),
                AdamWConfig(weight_decay=0.1, clip_norm=1.0))
    loader = PackingLoader(SyntheticCorpus(CorpusConfig(vocab=cfg.vocab,
                                                        seed=0)),
                           LoaderConfig(rows=rows, seq_len=SEQ_LEN))
    return Trainer(model, opt, loader,
                   TrainerConfig(steps=TRAIN_STEPS, log_every=TRAIN_STEPS),
                   mesh=mesh)


def compile_step(trainer, rows: int):
    """AOT-compile the trainer's jitted step for its real shapes (and
    shardings, on a mesh). Returns (compiled, seconds, planned bytes)."""
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(trainer.init_state, key)
    if trainer.state_sharding is not None:
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            state, trainer.state_sharding)
    batch = {k: jax.ShapeDtypeStruct((rows, SEQ_LEN), jnp.int32)
             for k in ("tokens", "positions", "segment_ids")}
    if trainer.mesh is not None:
        from repro.distributed import sharding as shd
        sh = shd.named(trainer.mesh, shd.batch_pspecs(batch, trainer.mesh))
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[k])
                 for k, v in batch.items()}
    t0 = time.perf_counter()
    compiled = trainer.step_fn.lower(state, batch).compile()
    secs = time.perf_counter() - t0
    m = compiled.memory_analysis()
    planned = (m.argument_size_in_bytes + m.output_size_in_bytes +
               m.temp_size_in_bytes - m.alias_size_in_bytes)
    return compiled, secs, planned


def run_train(label: str, trainer, compiled, rows: int, dev):
    """Train TRAIN_STEPS steps through Trainer.train on the compiled step,
    timing each step on the host clock up to block_until_ready."""
    import jax
    times = []

    def timed(state, batch):
        t0 = time.perf_counter()
        out = compiled(state, batch)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
        return out

    trainer.step_fn = timed
    state, hist = trainer.train(jax.random.PRNGKey(0), verbose=False)
    del state
    losses = [h["loss"] for h in hist]
    check(len(losses) == TRAIN_STEPS and all(np.isfinite(losses)),
          f"{label}: {len(losses)} finite losses {losses}")
    reading(f"{label}: warm step {np.median(times[1:]) * 1e3:.1f} ms "
            f"(median of steps 2..{TRAIN_STEPS}), first step "
            f"{times[0] * 1e3:.1f} ms, tokens/step {rows * SEQ_LEN} "
            f"({hist[0]['real_tokens']:.0f} real), peak_bytes_in_use "
            f"{peak_bytes(dev)}")
    return losses


def choose_rows(cfg, dev):
    """Largest row count whose compiled step plans within HEADROOM of the
    device's memory; returns (rows, trainer, compiled)."""
    limit = dev.memory_stats()["bytes_limit"]
    for rows in ROW_CANDIDATES:
        trainer = make_trainer(cfg, rows)
        compiled, secs, planned = compile_step(trainer, rows)
        reading(f"train step compile rows={rows}: {secs:.1f}s, plans "
                f"{planned} bytes of {limit} (limit x {HEADROOM})")
        if planned <= HEADROOM * limit:
            return rows, trainer, compiled
    raise RuntimeError(f"no row count in {ROW_CANDIDATES} fits the device")


def phase_train(cfg, dev):
    rows, trainer, compiled = choose_rows(
        dataclasses.replace(cfg, use_pallas=False), dev)
    xla = run_train(f"train xla rows={rows}", trainer, compiled, rows, dev)
    del trainer, compiled
    pcfg = dataclasses.replace(cfg, use_pallas=True)
    trainer = make_trainer(pcfg, rows)
    compiled, secs, planned = compile_step(trainer, rows)
    hlo = compiled.as_text()
    check("tpu_custom_call" in hlo, "pallas train step holds the Mosaic "
          "kernels (tpu_custom_call), not the interpreter")
    check(planned <= HEADROOM * dev.memory_stats()["bytes_limit"],
          f"pallas train step plans {planned} bytes within headroom")
    reading(f"train step compile pallas rows={rows}: {secs:.1f}s")
    pal = run_train(f"train pallas rows={rows}", trainer, compiled, rows,
                    dev)
    d = abs(xla[0] - pal[0])
    check(d <= LOSS_ATOL, f"step-1 loss xla {xla[0]:.6f} vs pallas "
          f"{pal[0]:.6f}: |delta| {d:.2e} <= {LOSS_ATOL}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def phase_serve(cfg, dev):
    """Serve in f32 with f32 matmuls: greedy tokens are compared exactly
    with a per-request loop, and a packed prefill reassociates the scan,
    so the comparison needs rounding far below the top-2 logit gap."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import ServeEngine
    from repro.models.lm import build_model

    cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    n_req, max_len = 16, 512
    prompts = [rng.integers(1, cfg.vocab, size=int(n)).astype(np.int32)
               for n in rng.integers(16, 240, size=n_req)]
    budgets = [int(b) for b in rng.integers(8, 25, size=n_req)]
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        eng = ServeEngine(model, params, num_slots=8, max_len=max_len,
                          prefill_rows=2, buckets=(128, 256),
                          max_segments=4)
        for p, b in zip(prompts, budgets):
            eng.submit(p, b)
        outs = eng.run()
        secs = time.perf_counter() - t0
        st = eng.stats
        check(all(eng.status[r] == "done" for r in range(n_req)),
              f"all {n_req} requests done")
        check(all(len(outs[r]) == budgets[r] for r in range(n_req)),
              "every request got its whole token budget")
        check(st.prefills >= 2 and st.midflight_refills >= 1,
              f"{st.prefills} packed prefills, {st.midflight_refills} "
              f"mid-flight refills")
        reading(f"serve: {st.generated} tokens, {st.decode_steps} decode "
                f"steps, {secs:.1f}s incl. compile, peak_bytes_in_use "
                f"{peak_bytes(dev)}")

        pad = 256
        prefill = jax.jit(model.prefill, static_argnames="max_len")
        decode = jax.jit(model.decode_step)
        # the first request and the last (admitted by a mid-flight refill)
        for rid in (0, n_req - 1):
            p, n = prompts[rid], len(prompts[rid])
            batch = {"tokens": jnp.asarray(np.pad(p, (0, pad - n)))[None],
                     "positions": jnp.arange(pad, dtype=jnp.int32)[None],
                     "segment_ids": jnp.asarray(
                         (np.arange(pad) < n).astype(np.int32))[None]}
            lg, cache, clen = prefill(params, batch, max_len=max_len)
            tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
            ref = [int(tok[0, 0])]
            for t in range(budgets[rid] - 1):
                lg, cache = decode(params, cache, tok, clen + t)
                tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
                ref.append(int(tok[0, 0]))
            check(outs[rid] == ref, f"request {rid}: engine tokens equal "
                  f"the per-request prefill + decode_step loop "
                  f"({len(ref)} tokens)")


# ---------------------------------------------------------------------------
# four chips: sharded packed train step
# ---------------------------------------------------------------------------

def phase_sharded_train(cfg, dev):
    from repro.launch.mesh import make_local_mesh
    rows, trainer, compiled = choose_rows(cfg, dev)
    one = run_train(f"train one chip rows={rows}", trainer, compiled, rows,
                    dev)
    del trainer, compiled
    mesh = make_local_mesh(model_axis=2)
    trainer = make_trainer(cfg, rows, mesh=mesh)
    compiled, secs, planned = compile_step(trainer, rows)
    hlo = compiled.as_text()
    coll = {op: hlo.count(op) for op in ("all-reduce", "all-gather",
                                         "reduce-scatter", "all-to-all")}
    reading(f"sharded step compile on mesh {dict(mesh.shape)}: {secs:.1f}s, "
            f"plans {planned} bytes per device; collectives {coll}")
    check(sum(coll.values()) > 0, "sharded step HLO holds collectives")
    shard = run_train(f"train mesh {dict(mesh.shape)} rows={rows}", trainer,
                      compiled, rows, dev)
    for i, (a, b) in enumerate(zip(one, shard)):
        check(abs(a - b) <= LOSS_ATOL, f"step-{i + 1} loss one chip {a:.6f} "
              f"vs mesh {b:.6f}: |delta| {abs(a - b):.2e} <= {LOSS_ATOL}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded train step on 4 chips")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s), JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    dev = devs[0]

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.compile_cache import use_compile_cache
    from repro.configs.base import get_config

    print(f"device {dev.platform} {dev.device_kind} x{len(devs)}; compile "
          f"cache {use_compile_cache()}", flush=True)
    cfg = dataclasses.replace(get_config("mamba-110m"), dtype="bfloat16")
    phases = ([("sharded_train", phase_sharded_train)] if args.chips == 4
              else [("kernels", phase_kernels), ("train", phase_train),
                    ("serve", phase_serve)])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn(cfg, dev)
        reading(f"phase {name} done in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
