"""Benchmark harness — one function per paper table/figure.

  fig2   SSM operator duration vs seqlen (paper Fig 2: the 2^n staircase)
  fig5   training throughput: single-sequence vs padding vs pack (Fig 5)
  fig6   per-operator speedup, padding vs pack at matched tokens (Fig 6)
  disc   packing-policy padding rates + sort overhead (paper §5)
  roof   roofline table from the dry-run artifacts (EXPERIMENTS.md §Roofline)
  serve  serving throughput: padded-wave vs packed-continuous batching
         (launch/serve.py engine; emits BENCH_serve.json)
  train  gated training benchmark: single vs padded vs packed train steps
         in f32 and bf16, real-vs-buffer tok/s + padding_rate per row
         (emits BENCH_train.json)

Output: ``name,us_per_call,derived`` CSV rows (plus commented context lines).
CPU timings are for *ratios* (the paper's A100 wall-clock is not reproducible
here); the structural effects — padding-rate, token-density, step-count —
are hardware-independent and checked against the paper's numbers.

Run: PYTHONPATH=src python -m benchmarks.run [fig2 fig5 fig6 disc roof]
(add ``--obs-trace PATH`` to any selection to export a Chrome trace-event
JSON of the serve packed_obs engine + train timing rounds — repro/obs)
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.timing import interleaved_min_of_rounds


def _timeit(fn, *args, reps=3, warmup=1):
    best, _ = interleaved_min_of_rounds(
        [("cell", lambda: fn(*args))], rounds=reps, warmup=warmup)
    return best["cell"]         # us


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}")


# ---------------------------------------------------------------------------
# Fig 2 — SSM operator profile vs seqlen × scan schedule
# ---------------------------------------------------------------------------

BENCH_RECORDS = []          # machine-readable mirror of the scan CSV rows
# output path override so `make bench-scan` can write a fresh file next to
# the committed baseline instead of clobbering it (see Makefile)
BENCH_JSON = os.environ.get("BENCH_SCAN_JSON", "BENCH_scan.json")
# BENCH_SMOKE=1 (the `make bench-smoke` / CI lane): tiny shapes and short
# workloads — the JSON structure is checked (compare.py --schema), timings
# are NOT gated, so the job stays minutes-bounded on a cold cache
SMOKE = os.environ.get("BENCH_SMOKE", "") not in ("", "0")

# --obs-trace PATH (stripped from argv in main()): record host span traces
# for the obs-instrumented serve mode and the train timing rounds, and
# export ONE Chrome trace-event JSON (Perfetto-loadable) at PATH
OBS_TRACE = None
_OBS = None


def _obs():
    """Process-wide Obs handle: recording iff --obs-trace was given."""
    global _OBS
    if _OBS is None:
        from repro.obs import Obs
        _OBS = Obs.on() if OBS_TRACE else Obs.off()
    return _OBS


def _bench(op, shape, schedule, us, tokens):
    BENCH_RECORDS.append({"op": op, "shape": shape, "schedule": schedule,
                          "us_per_call": round(us, 1),
                          "tok_per_s": round(tokens / (us / 1e6), 1)})


def _packed_positions(L, seg=100):
    """Packed position ids: ≥2 segments at every benchmarked L (smallest is
    256), with boundaries straddling the power-of-two scan chunks."""
    lens = [seg] * (L // seg) + ([L % seg] if L % seg else [])
    return jnp.asarray(np.concatenate([np.arange(n) for n in lens])[None],
                       jnp.int32)


def fig2_ssm_operator_profile():
    """Paper Fig 2 reframed for schedules: the SSM operator's duration vs
    seqlen under each scan schedule at matched shapes, with PACKED positions
    (multi-segment rows) so the reset handling is exercised in every cell.

      chunked         materialize (B,L,D,N), chunk-carried associative scan
                      (the pre-blocked default)
      blocked         SSD-style block-parallel schedule, backend-default
                      in-chunk evaluator (core/ssm.py::_blocked_ssm)
      blocked_matmul  same schedule, explicit M @ b einsum contraction
                      (the MXU-shaped form)
      fused_seq       single sequential scan, y fused
      mamba2_blocked  head-structured (scalar per-head decay) blocked
                      schedule at MATCHED channels (D = H·dh) — the decay
                      matrix is (T,T) per head and the chunk evaluates as
                      one (T,T)·(T,dh·N) matmul
                      (core/ssm.py::selective_scan_heads)
      mamba2_dual     same schedule, C·Bᵀ attention-like dual-form in-chunk
                      evaluator (intra="dual")
      mamba2w_*       wide-head family (H=2, dh=128) at matched channels
                      with a small chunk (T=16) — the dh ≫ T regime where
                      the dual form's T²·(dh+N) beats quad's T²·dh·N

    The blocked_noreset row repeats `blocked` with reset-free positions:
    its delta vs `blocked` is the cost of PackMamba reset-correctness
    (paper's claim: ~zero). A final comment row greps the compiled HLO for
    a (B, L, D, N)-shaped buffer — the peak-memory evidence that `blocked`
    (unlike `chunked`) never materializes the full decay/state trajectory
    (and likewise no (B, L, H, dh, N) buffer for mamba2_blocked).
    """
    print("# fig2: selective_scan duration vs seqlen x schedule "
          "(B=1, D=256, N=16, packed segments ~300; mamba2 rows: H=4 "
          "dh=64, mamba2w rows: H=2 dh=128, both at matched channels)")
    from repro.core.ssm import selective_scan, selective_scan_heads
    rng = np.random.default_rng(0)
    D, N = 256, 16
    H2 = 4
    P2 = D // H2
    H2w, P2w = 2, D // 2            # wide heads: dh = 128 ≫ T = 16
    A = -jnp.exp(jnp.asarray(rng.normal(size=(D, N)), jnp.float32))
    A2 = -jnp.exp(jnp.asarray(rng.normal(size=(H2,)), jnp.float32))
    A2w = -jnp.exp(jnp.asarray(rng.normal(size=(H2w,)), jnp.float32))
    Dk = jnp.ones((D,), jnp.float32)
    D2k = jnp.ones((H2,), jnp.float32)
    D2wk = jnp.ones((H2w,), jnp.float32)
    scheds = [
        ("chunked", dict(method="chunked", chunk=256)),
        ("blocked", dict(method="blocked", chunk=128)),
        ("blocked_matmul", dict(method="blocked", chunk=16,
                                intra="matmul")),
        ("fused_seq", dict(method="fused_seq")),
    ]

    for L in ([256] if SMOKE else [256, 512, 1024, 2048, 4096]):
        u = jnp.asarray(rng.normal(size=(1, L, D)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.1, 0.5, (1, L, D)), jnp.float32)
        Bm = jnp.asarray(rng.normal(size=(1, L, N)), jnp.float32)
        Cm = jnp.asarray(rng.normal(size=(1, L, N)), jnp.float32)
        u2 = u.reshape(1, L, H2, P2)
        dt2 = jnp.asarray(rng.uniform(0.1, 0.5, (1, L, H2)), jnp.float32)
        u2w = u.reshape(1, L, H2w, P2w)
        dt2w = jnp.asarray(rng.uniform(0.1, 0.5, (1, L, H2w)), jnp.float32)
        pos = _packed_positions(L)
        pos_flat = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (1, L))
        shape = f"B1_L{L}_D{D}_N{N}"

        def mk1(kw):
            return jax.jit(lambda u, dt, Bm, Cm, pos, kw=tuple(kw.items()):
                           selective_scan(u, dt, A, Bm, Cm, Dk, pos,
                                          **dict(kw)))

        def mk2(kw, A2=A2, D2k=D2k):
            return jax.jit(lambda u, dt, Bm, Cm, pos, kw=tuple(kw.items()):
                           selective_scan_heads(u, dt, A2, Bm, Cm, D2k, pos,
                                                **dict(kw)))

        # each cell: (name, jitted fn, args)
        cells = [(name, mk1(kw), (u, dt, Bm, Cm, pos))
                 for name, kw in scheds]
        cells.append(("blocked_noreset",
                      mk1(dict(method="blocked", chunk=128)),
                      (u, dt, Bm, Cm, pos_flat)))
        cells.append(("mamba2_blocked",
                      mk2(dict(method="blocked", chunk=64)),
                      (u2, dt2, Bm, Cm, pos)))
        cells.append(("mamba2_dual",
                      mk2(dict(method="blocked", chunk=64, intra="dual")),
                      (u2, dt2, Bm, Cm, pos)))
        cells.append(("mamba2_noreset",
                      mk2(dict(method="blocked", chunk=64)),
                      (u2, dt2, Bm, Cm, pos_flat)))
        # the dh ≫ T regime: quad must pay T²·dh·N, dual only T²·(dh+N)
        cells.append(("mamba2w_quad",
                      mk2(dict(method="blocked", chunk=16, intra="quad"),
                          A2w, D2wk),
                      (u2w, dt2w, Bm, Cm, pos)))
        cells.append(("mamba2w_dual",
                      mk2(dict(method="blocked", chunk=16, intra="dual"),
                          A2w, D2wk),
                      (u2w, dt2w, Bm, Cm, pos)))
        # interleave schedules round-robin: min-of-rounds is robust to the
        # machine-load drift that would bias per-schedule timing blocks
        # (shared protocol: benchmarks/timing.py)
        best, _ = interleaved_min_of_rounds(
            [(name, (lambda fn=fn, args=args: fn(*args)))
             for name, fn, args in cells], rounds=7)
        for name, fn, args in cells:
            us = best[name]
            tag = " (reset-free baseline)" if name.endswith("noreset") \
                else ""
            _row(f"fig2/ssm_{name}_L{L}", us,
                 f"{L / (us / 1e6):.0f} tok/s{tag}")
            _bench("selective_scan", shape, name, us, L)
    if SMOKE:       # the HLO evidence below is compile-heavy; smoke skips it
        return
    # ---- peak-memory evidence: no (B, L, D, N) buffer in the blocked HLO
    L = 2048
    u = jnp.asarray(rng.normal(size=(1, L, D)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 0.5, (1, L, D)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(1, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(1, L, N)), jnp.float32)
    pos = _packed_positions(L)
    full = f"f32[1,{L},{D},{N}]"
    for name, kw in (("chunked", dict(method="chunked", chunk=256)),
                     ("blocked", dict(method="blocked", chunk=128)),
                     ("blocked_matmul", dict(method="blocked", chunk=16,
                                             intra="matmul"))):
        hlo = jax.jit(lambda u, dt, Bm, Cm, pos, kw=tuple(kw.items()):
                      selective_scan(u, dt, A, Bm, Cm, Dk, pos,
                                     **dict(kw))).lower(
            u, dt, Bm, Cm, pos).compile().as_text()
        print(f"# fig2 memory: {name} HLO contains (B,L,D,N)={full} "
              f"buffer: {full in hlo}")
    u2 = u.reshape(1, L, H2, P2)
    dt2 = jnp.asarray(rng.uniform(0.1, 0.5, (1, L, H2)), jnp.float32)
    full2 = f"f32[1,{L},{H2},{P2},{N}]"
    hlo2 = jax.jit(lambda u, dt, Bm, Cm, pos:
                   selective_scan_heads(u, dt, A2, Bm, Cm, D2k, pos,
                                        method="blocked", chunk=64)).lower(
        u2, dt2, Bm, Cm, pos).compile().as_text()
    print(f"# fig2 memory: mamba2_blocked HLO contains (B,L,H,dh,N)="
          f"{full2} buffer: {full2 in hlo2}")


# ---------------------------------------------------------------------------
# Fig 5 — training throughput: single vs padding vs pack
# ---------------------------------------------------------------------------

def _tiny_mamba(vocab=256, d_model=128, n_layers=4):
    from repro.configs.base import get_config
    cfg = get_config("mamba-110m")
    return dataclasses.replace(cfg, vocab=vocab, d_model=d_model,
                               n_layers=n_layers, dtype="float32",
                               scan_chunk=128)


def fig5_training_throughput(seq_len=512, n_stream=48):
    """Paper Fig 5 protocol: same sequence stream through the three
    regimes; throughput = corpus tokens / wall time. Paper (A100, bf16):
    pack/single = 3.06× (1.4B), 5.05× (110m); pack always beats padding.
    Derived: tok/s and speedup vs single-sequence."""
    print(f"# fig5: training throughput, tiny-mamba, seq_len={seq_len}, "
          f"{n_stream} sequences per batch")
    from repro.core.packing import pack, pad_to_max
    from repro.models.lm import build_model
    from repro.optim.adamw import AdamW, constant_schedule
    from repro.train.trainer import make_train_step
    from repro.data.dataset import SyntheticCorpus, CorpusConfig

    cfg = _tiny_mamba()
    model = build_model(cfg)
    opt = AdamW(constant_schedule(1e-3))
    step = jax.jit(make_train_step(model, opt))
    corpus = SyntheticCorpus(CorpusConfig(
        vocab=cfg.vocab, seed=0, len_min=seq_len // 8, len_max=seq_len,
        mu=float(np.log(seq_len / 3.0)), sigma=0.6))
    seqs = corpus.batch_of_sequences(0, n_stream)
    total_tokens = sum(len(s) for s in seqs)

    def regime_batches(mode):
        if mode == "pack":
            pb = pack(seqs, seq_len)
            return [{"tokens": pb.tokens, "positions": pb.positions,
                     "segment_ids": pb.segment_ids}]
        if mode == "pad":
            pb = pad_to_max(seqs, seq_len)
            return [{"tokens": pb.tokens, "positions": pb.positions,
                     "segment_ids": pb.segment_ids}]
        out = []
        for s in seqs:                      # single: one sequence per step
            cap = 1 << (len(s) - 1).bit_length()
            pb = pad_to_max([s], cap)
            out.append({"tokens": pb.tokens, "positions": pb.positions,
                        "segment_ids": pb.segment_ids})
        return out

    results = {}
    for mode in ("single", "pad", "pack"):
        batches = regime_batches(mode)
        params = model.init(jax.random.PRNGKey(0))
        state = {"params": params, "opt": opt.init(params)}
        # warmup compile for every distinct shape
        for b in {bb["tokens"].shape: bb for bb in batches}.values():
            state, _ = step(state, b)
        jax.block_until_ready(jax.tree.leaves(state["params"])[0])
        t0 = time.perf_counter()
        for b in batches:
            state, m = step(state, b)
        jax.block_until_ready(jax.tree.leaves(state["params"])[0])
        dt = time.perf_counter() - t0
        results[mode] = dt
        _row(f"fig5/{mode}", dt * 1e6,
             f"{total_tokens / dt:.0f} tok/s over {len(batches)} step(s)")
    _row("fig5/speedup_pack_vs_single",
         results["single"] / results["pack"] * 100,
         f"{results['single'] / results['pack']:.2f}x (paper: 3.06x@1.4B "
         f"5.05x@110m bf16)")
    _row("fig5/speedup_pack_vs_pad", results["pad"] / results["pack"] * 100,
         f"{results['pad'] / results['pack']:.2f}x")


# ---------------------------------------------------------------------------
# train — the paper's experiment as a gated benchmark: full train steps,
# single vs padded vs packed, f32 vs bf16 (emits BENCH_train.json)
# ---------------------------------------------------------------------------

TRAIN_RECORDS = []
TRAIN_JSON = os.environ.get("BENCH_TRAIN_JSON", "BENCH_train.json")


def train_throughput(seq_len=512, rows=4, steps=4):
    """PackMamba's headline experiment, gated: the SAME lognormal sequence
    stream through three training regimes as full train steps (fwd+bwd+
    AdamW), in f32 and in the bf16 mixed-precision lane (activations bf16,
    scan carries and loss reduction f32 — models/lm.py). Paper (A100,
    bf16): pack/single 3.06× (1.4B) / 5.05× (110m); pack > pad always.

    The three regimes are the paper's three pipelines under jit's static-
    shape discipline (every shape is warmed before timing):

      single  batch-1 fixed-context pipeline: one sequence per step,
              padded to the compiled (1, seq_len) buffer. (The pow2-
              bucketed batch-1 variant is fig5 / loader mode="single" —
              published there; on a CPU box it under-represents the
              paper's GPU underutilization cost.)
      pad     standard dynamic batch padding: `rows` sequences per step,
              one per row, padded to the longest in the batch rounded up
              to a power of two (bounded compiled-shape count).
      pack    PackingLoader first_fit_decreasing packed (rows, seq_len)
              buffers.

    tok/s = stream (real) tokens / wall; every row also reports the
    buffer-token rate and the padding_rate connecting them — the packed
    regime wins precisely because its buffer work is ~all real."""
    if SMOKE:
        seq_len, rows, steps = 256, 2, 2
    print(f"# train: single vs pad vs pack train steps x f32/bf16, "
          f"tiny-mamba, rows={rows}, seq_len={seq_len}, {steps} stream "
          f"draws, policy=first_fit_decreasing")
    from repro.core.packing import pad_to_max
    from repro.data.dataset import SyntheticCorpus, CorpusConfig
    from repro.data.packing_loader import PackingLoader, LoaderConfig
    from repro.models.lm import build_model
    from repro.optim.adamw import AdamW, constant_schedule
    from repro.train.trainer import make_train_step

    # lognormal with mass well below seq_len (paper Fig 1: mean ~646 at a
    # 4096 capacity) — the regime where packing pays and fixed-context
    # padding hurts
    corpus = SyntheticCorpus(CorpusConfig(
        vocab=256, seed=0, len_min=seq_len // 16, len_max=seq_len,
        mu=float(np.log(seq_len / 4.5)), sigma=0.45))
    loader = PackingLoader(corpus, LoaderConfig(
        rows=rows, seq_len=seq_len, mode="pack",
        policy="first_fit_decreasing"))
    n_draw = loader._n_draw()
    streams = [corpus.batch_of_sequences(s, n_draw) for s in range(steps)]

    def as_batch(pb):
        return {"tokens": pb.tokens, "positions": pb.positions,
                "segment_ids": pb.segment_ids}

    def batches_for(mode):
        if mode == "pack":
            return [loader.batch(s) for s in range(steps)]
        out = []
        for seqs in streams:
            if mode == "single":
                out += [as_batch(pad_to_max([s], seq_len)) for s in seqs]
            else:
                for i in range(0, len(seqs), rows):
                    group = seqs[i:i + rows]
                    cap = 1 << (max(len(s) for s in group) - 1).bit_length()
                    out.append(as_batch(pad_to_max(group, cap)))
        return out

    shape = f"tiny-mamba_rows{rows}x{seq_len}"
    real_tps = {}
    tr = _obs().tracer          # records per-round spans iff --obs-trace
    for mode in ("single", "pad", "pack"):
        bs = batches_for(mode)
        real = sum(int((b["segment_ids"] > 0).sum()) for b in bs)
        buf = sum(int(b["tokens"].size) for b in bs)
        pad_rate = 1.0 - real / buf
        for dtag, dname in (("f32", "float32"), ("bf16", "bfloat16")):
            cfg = dataclasses.replace(_tiny_mamba(), dtype=dname)
            model = build_model(cfg)
            opt = AdamW(constant_schedule(1e-3))
            step = jax.jit(make_train_step(model, opt))
            params = model.init(jax.random.PRNGKey(0))
            state = {"params": params, "opt": opt.init(params)}
            # warmup compile for every distinct shape (pad's remainder
            # group adds at most one)
            for b in {bb["tokens"].shape: bb for bb in bs}.values():
                state, _ = step(state, b)
            jax.block_until_ready(jax.tree.leaves(state["params"])[0])
            best_dt = np.inf
            sched = f"{mode}_{dtag}"
            for rnd in range(2):            # min-of-rounds vs load spikes
                t0 = time.perf_counter()
                for b in bs:
                    state, m = step(state, b)
                jax.block_until_ready(jax.tree.leaves(state["params"])[0])
                t1 = time.perf_counter()
                tr.complete(f"bench.train.{sched}", t0, t1, track="bench",
                            round=rnd, steps=len(bs), real_tokens=real)
                best_dt = min(best_dt, t1 - t0)
            real_tps[sched] = real / best_dt
            TRAIN_RECORDS.append({
                "op": "train", "shape": shape, "schedule": sched,
                "us_per_call": round(best_dt / len(bs) * 1e6, 1),
                "tok_per_s": round(real / best_dt, 1),
                "real_tok_per_s": round(real / best_dt, 1),
                "buffer_tok_per_s": round(buf / best_dt, 1),
                "padding_rate": round(pad_rate, 4)})
            _row(f"train/{sched}", best_dt / len(bs) * 1e6,
                 f"{real / best_dt:.0f} real tok/s "
                 f"({buf / best_dt:.0f} buffer, "
                 f"padding {pad_rate * 100:.1f}%, "
                 f"{len(bs)} step(s))")
    for dtag in ("f32", "bf16"):
        s, p, k = (real_tps[f"{m}_{dtag}"] for m in ("single", "pad",
                                                     "pack"))
        _row(f"train/speedup_pack_vs_single_{dtag}", k / s * 100,
             f"{k / s:.2f}x (paper bf16: 3.06x@1.4B 5.05x@110m); "
             f"pack/pad {k / p:.2f}x")


# ---------------------------------------------------------------------------
# Fig 6 — kernel-level speedup, padding vs pack
# ---------------------------------------------------------------------------

def fig6_kernel_speedup(seq_len=512):
    """Paper Fig 6: with padding as baseline, packing shrinks GEMM + SSM
    time by the token-density ratio; conv1d (memory-bound) gains less.
    We time each operator fwd+bwd at 'padding' shapes (many mostly-empty
    rows) vs 'pack' shapes (few dense rows) for the SAME real tokens."""
    print(f"# fig6: per-operator fwd+bwd time, padding vs pack "
          f"(matched real tokens, seq_len={seq_len})")
    from repro.core.packing import pack, pad_to_max
    from repro.data.dataset import SyntheticCorpus, CorpusConfig
    from repro.kernels.ops import selective_scan, conv1d_pack
    rng = np.random.default_rng(0)
    corpus = SyntheticCorpus(CorpusConfig(
        vocab=256, seed=0, len_min=seq_len // 8, len_max=seq_len,
        mu=float(np.log(seq_len / 3.0)), sigma=0.6))
    seqs = corpus.batch_of_sequences(0, 24)
    pb_pack = pack(seqs, seq_len)
    pb_pad = pad_to_max(seqs, seq_len)
    D, N, W = 256, 16, 4
    A = -jnp.exp(jnp.asarray(rng.normal(size=(D, N)), jnp.float32))
    Dk = jnp.ones((D,), jnp.float32)
    wconv = jnp.asarray(rng.normal(size=(W, D)), jnp.float32)
    wproj = jnp.asarray(rng.normal(size=(D, 2 * D)) / 16, jnp.float32)

    def mk(pb):
        Bz, L = pb.tokens.shape
        return dict(
            x=jnp.asarray(rng.normal(size=(Bz, L, D)), jnp.float32),
            dt=jnp.asarray(rng.uniform(0.1, 0.5, (Bz, L, D)), jnp.float32),
            Bm=jnp.asarray(rng.normal(size=(Bz, L, N)), jnp.float32),
            Cm=jnp.asarray(rng.normal(size=(Bz, L, N)), jnp.float32),
            pos=pb.positions)

    ssm = jax.jit(jax.grad(lambda x, d: (selective_scan(
        x, d["dt"], A, d["Bm"], d["Cm"], Dk, d["pos"],
        backend="xla", xla_chunk=128) ** 2).sum()))
    conv = jax.jit(jax.grad(lambda x, d: (conv1d_pack(
        x, wconv, None, d["pos"], backend="xla") ** 2).sum()))
    gemm = jax.jit(jax.grad(lambda x: ((x @ wproj) ** 2).sum()))

    speed = {}
    for op_name, fn, needs in (("ssm", ssm, True), ("conv1d", conv, True),
                               ("gemm", gemm, False)):
        times = {}
        for mode, pb in (("pad", pb_pad), ("pack", pb_pack)):
            d = mk(pb)
            args = (d["x"], d) if needs else (d["x"],)
            times[mode] = _timeit(fn, *args)
            _row(f"fig6/{op_name}_{mode}", times[mode],
                 f"rows={pb.tokens.shape[0]}")
        speed[op_name] = times["pad"] / times["pack"]
        _row(f"fig6/{op_name}_speedup", speed[op_name] * 100,
             f"{speed[op_name]:.2f}x (pad/pack)")
    print(f"# fig6 note: paper fwd+bwd 3.91x overall; GEMM+SSM gain ~= "
          f"token-density ratio, conv1d (memory-bound) gains less — here "
          f"ssm {speed['ssm']:.2f}x gemm {speed['gemm']:.2f}x "
          f"conv {speed['conv1d']:.2f}x")


# ---------------------------------------------------------------------------
# serve — padded-wave vs packed-continuous serving throughput
# ---------------------------------------------------------------------------

SERVE_RECORDS = []
SERVE_JSON = os.environ.get("BENCH_SERVE_JSON", "BENCH_serve.json")


def serve_throughput(n_requests=32, max_new=16, slots=8):
    """Serving throughput at paper-like prompt-length spreads: the padded
    synchronous-wave baseline (every prompt left-padded to the wave max,
    decode drains before the next wave admits) vs the packed continuous
    engine (prompts packed into shape-bucketed prefill buffers, per-segment
    state handoff, mid-flight slot refill), with and without prefill/decode
    OVERLAP (async prefill dispatch + TTFT-bounded admission). All modes
    greedy-decode the same requests on the same tiny mamba; tok/s =
    generated tokens / wall time after a full warm-up pass (compiles
    excluded from all sides — the bucket evidence line shows the packed
    side's compile count is bounded by the bucket list, not the number of
    distinct prompt lengths). Packed rows also emit p50/p95 TTFT
    (submit→first token, measured at host observability) accumulated over
    the timed rounds. The packed_obs row repeats packed_overlap with the
    host span tracer RECORDING (Obs.on()) — its delta vs packed_overlap,
    serve/obs_overhead_pct, is the measured cost of enabled observability
    (< 3% expected: two host timestamps per engine phase)."""
    rounds = 3
    if SMOKE:
        n_requests, max_new, slots, rounds = 10, 6, 4, 2
    print(f"# serve: padded-wave vs packed-continuous vs packed-overlap "
          f"vs packed-guarded vs packed-obs, tiny-mamba, {n_requests} "
          f"requests, {slots} slots, max_new={max_new}")
    from repro.models.lm import build_model
    from repro.launch.serve import ServeEngine
    from repro.obs import Obs

    cfg = _tiny_mamba()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # lognormal-ish spread of prompt lengths (the paper's variable-length
    # serving regime), clipped to the bucket range; output budgets vary
    # too — padded waves drain to the slowest row, continuous refills
    lens = np.clip(np.exp(rng.normal(np.log(24), 0.7, n_requests)),
                   4, 96).astype(int)
    budgets = rng.integers(max(2, max_new // 4), 2 * max_new,
                           size=n_requests).tolist()
    prompts = [rng.integers(1, cfg.vocab, size=int(n)).astype(np.int32)
               for n in lens]
    max_len = 160
    shape = f"tiny-mamba_reqs{n_requests}_slots{slots}_new{max_new}"

    def run_padded(eng):
        gen = 0
        for i in range(0, len(prompts), slots):
            outs = eng.decode_batch(prompts[i:i + slots],
                                    budgets[i:i + slots])
            gen += sum(len(o) for o in outs)
        return gen

    def run_packed(eng):
        rids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        outs = eng.run()
        return sum(len(outs[r]) for r in rids)

    # the overlap row isolates ASYNC PREFILL at a matched admission policy
    # (no TTFT target): this closed-loop workload submits everything up
    # front, so a TTFT override only converts batched prefills into many
    # small ones — the latency policy pays off on open-loop traffic with
    # arrival gaps, and is covered by the scripted-clock tests instead
    kw = dict(buckets=(32, 64, 128), max_segments=4)
    modes = [("padded_wave", run_padded,
              ServeEngine(model, params, slots, max_len)),
             ("packed_continuous", run_packed,       # the PR-3 reference
              ServeEngine(model, params, slots, max_len, overlap=False,
                          **kw)),
             ("packed_overlap", run_packed,          # async prefill dispatch
              ServeEngine(model, params, slots, max_len, overlap=True,
                          **kw)),
             ("packed_guarded", run_packed,          # + numerical guard
              # rails: per-step finiteness probes on decode logits and
              # harvested prefill states (the fault-tolerance layer's
              # quarantine path); the probe is fused into the jitted step,
              # so the expected cost is <2% of decode throughput
              ServeEngine(model, params, slots, max_len, overlap=True,
                          guard=True, **kw)),
             ("packed_obs", run_packed,              # + host span tracer ON
              # the observability cost row: same engine as packed_overlap
              # but with per-request lifecycle + engine-phase spans being
              # RECORDED; exported when --obs-trace is given
              ServeEngine(model, params, slots, max_len, overlap=True,
                          obs=_obs() if OBS_TRACE else Obs.on(), **kw))]
    for name, runner, eng in modes:            # warm-up: compile all shapes
        runner(eng)
        eng.stats = type(eng.stats)()          # count the timed rounds only
    # interleave timed rounds (min-of-rounds, same protocol as fig2 — CPU
    # wall clock is noisy and the modes must not sit in different load
    # regimes); warm-up already happened above so stats stay clean. TTFT
    # percentiles aggregate over every timed round (latency needs the
    # distribution, not the best round).
    best, gens = interleaved_min_of_rounds(
        [(name, (lambda runner=runner, eng=eng: runner(eng)))
         for name, runner, eng in modes], rounds=rounds, warmup=0)
    results = {name: best[name] / 1e6 for name, _, _ in modes}
    for name, runner, eng in modes:
        dt = results[name]
        gen = gens[name]
        rec = {"op": "serve", "shape": shape, "schedule": name,
               "us_per_call": round(dt * 1e6, 1),
               "tok_per_s": round(gen / dt, 1)}
        st = eng.stats
        pct = st.ttft_percentiles()
        extra = f"{gen / dt:.0f} tok/s"
        if pct:
            rec["ttft_p50_ms"] = round(pct["p50"], 2)
            rec["ttft_p95_ms"] = round(pct["p95"], 2)
            extra += (f" ttft p50 {pct['p50']:.1f}ms p95 "
                      f"{pct['p95']:.1f}ms")
        # engine-phase wall-time split (averaged per timed round) — makes a
        # packed-vs-padded throughput gap attributable: is the continuous
        # engine losing time in prefill sync, fused decode, or host-side
        # scheduling? padded_wave bypasses step(), so its split is zero.
        rec["prefill_ms"] = round(st.prefill_ms / rounds, 2)
        rec["chunk_ms"] = round(st.chunk_ms / rounds, 2)
        rec["decode_ms"] = round(st.decode_ms / rounds, 2)
        rec["host_ms"] = round(st.host_ms / rounds, 2)
        if name == "packed_obs":
            rec["obs_overhead_pct"] = round(
                (results["packed_obs"] / results["packed_overlap"] - 1.0)
                * 100, 2)
        _row(f"serve/{name}", dt * 1e6, extra)
        SERVE_RECORDS.append(rec)
        if name == "packed_overlap":
            print(f"# serve overlap evidence: "
                  f"{st.overlapped_prefills // rounds} of "
                  f"{st.prefills // rounds} prefills/run stayed in flight "
                  f"across ≥1 decode step")
        if name == "packed_continuous":
            print(f"# serve compile evidence: {len(st.buckets)} prefill "
                  f"shape(s) for {len(set(map(int, lens)))} distinct prompt "
                  f"lengths; {st.prefills // rounds} prefills "
                  f"({st.midflight_refills // rounds} mid-flight), "
                  f"{st.decode_steps // rounds} decode steps per run")
            print(f"# serve time split (per round): prefill "
                  f"{st.prefill_ms / rounds:.0f}ms decode "
                  f"{st.decode_ms / rounds:.0f}ms host "
                  f"{st.host_ms / rounds:.0f}ms — where a padded-wave gap "
                  f"lives")
    _row("serve/speedup_packed_vs_padded",
         results["padded_wave"] / results["packed_continuous"] * 100,
         f"{results['padded_wave'] / results['packed_continuous']:.2f}x")
    _row("serve/speedup_overlap_vs_continuous",
         results["packed_continuous"] / results["packed_overlap"] * 100,
         f"{results['packed_continuous'] / results['packed_overlap']:.2f}x "
         f"(>= 1.0 expected: overlap must not lose throughput)")
    guard_pct = (results["packed_guarded"] / results["packed_overlap"]
                 - 1.0) * 100
    _row("serve/guard_overhead_pct", guard_pct,
         f"{guard_pct:+.1f}% decode throughput for the finiteness probes "
         f"(< 2% expected: the probe is a fused all-reduce per step)")
    obs_pct = (results["packed_obs"] / results["packed_overlap"]
               - 1.0) * 100
    _row("serve/obs_overhead_pct", obs_pct,
         f"{obs_pct:+.1f}% decode throughput with the host span tracer "
         f"recording (< 3% expected: two perf_counter stamps per engine "
         f"phase + per-request lifecycle spans)")


def serve_open_loop(n_requests=48, max_new=16, slots=8):
    """Open-loop (Poisson-arrival) serving: requests arrive on a seeded
    exponential-gap schedule instead of all-at-once, which is where
    scheduler POLICY (early admission, bucket choice, prefill pipelining)
    actually shows up — a closed-loop run hides TTFT behind an always-full
    queue. Offered load is calibrated to ~1.5× the engine's closed-loop
    service rate (measured on this box during warm-up) — far enough past
    saturation that the admission queue stays non-empty, which is the
    regime where bucket CHOICE exists at all — then the SAME
    arrival schedule is replayed for the v1-equivalent scheduler
    (``open_packed_overlap``: one in-flight prefill, smallest-fit buckets,
    no TTFT policy) and the v2 scheduler (``open_scheduler_v2``: prefill
    pool of 2, TTFT-aware bucket upgrades, early admission) — matched
    offered load, so tok/s and ttft_p95 are directly comparable and a
    scheduler cannot buy throughput by silently queueing latency."""
    rounds = 4                 # timed rounds are ~1s each; the p95 needs
    #                            rounds*n_requests samples to sit still
    if SMOKE:
        n_requests, max_new, slots, rounds = 10, 6, 4, 1
    print(f"# serve_open: Poisson arrivals, v1 vs v2 scheduler, "
          f"tiny-mamba, {n_requests} requests, {slots} slots, "
          f"max_new={max_new}")
    from repro.models.lm import build_model
    from repro.launch.serve import ServeEngine

    cfg = _tiny_mamba()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    lens = np.clip(np.exp(rng.normal(np.log(24), 0.7, n_requests)),
                   4, 96).astype(int)
    budgets = rng.integers(max(2, max_new // 4), 2 * max_new,
                           size=n_requests).tolist()
    prompts = [rng.integers(1, cfg.vocab, size=int(n)).astype(np.int32)
               for n in lens]
    max_len = 160
    shape = f"tiny-mamba_open_reqs{n_requests}_slots{slots}_new{max_new}"
    kw = dict(buckets=(32, 64, 128), max_segments=4, overlap=True)
    modes = [("open_packed_overlap",          # the pre-v2 scheduler
              ServeEngine(model, params, slots, max_len, **kw)),
             ("open_scheduler_v2",            # pipelined + TTFT-aware
              ServeEngine(model, params, slots, max_len,
                          max_inflight_prefills=2, bucket_policy="ttft",
                          **kw))]
    # v2 carries NO fixed target_ttft_ms: the bucket policy self-calibrates
    # on its own measured TTFT p50 (_choose_bucket's fallback), and the
    # PR-5 early-admit override stays off — a fixed target below what the
    # box can deliver would force panic admissions of tiny fragmented
    # batches, which is a misconfiguration, not a scheduler property

    def run_closed(eng):
        rids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        eng.run()
        return sum(len(eng.outputs[r]) for r in rids)

    for _, eng in modes:                  # warm-up: compile every shape
        run_closed(eng)
    # capacity measurement on WARM engines calibrates the offered load —
    # folding compiles in would understate capacity and leave the arrival
    # process too sparse to ever stress the scheduler
    t0 = time.perf_counter()
    for _, eng in modes:
        run_closed(eng)
    closed_dt = (time.perf_counter() - t0) / len(modes)
    rate = 1.5 * n_requests / closed_dt           # offered requests/sec
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    print(f"# serve_open offered load: {rate:.1f} req/s "
          f"(1.5x measured closed-loop capacity), last arrival "
          f"{arrivals[-1] * 1e3:.0f}ms")
    for _, eng in modes:
        eng.stats = type(eng.stats)()             # timed rounds only

    def run_open(eng):
        t0 = time.perf_counter()
        i = 0
        rids = []
        while True:
            now = time.perf_counter() - t0
            while i < len(prompts) and arrivals[i] <= now:
                rids.append(eng.submit(prompts[i], budgets[i]))
                i += 1
            busy = eng.step()
            if not busy:
                if i >= len(prompts):
                    break
                gap = arrivals[i] - (time.perf_counter() - t0)
                if gap > 0:                       # idle until next arrival
                    time.sleep(min(gap, 2e-3))
        return sum(len(eng.outputs[r]) for r in rids)

    best, gens = interleaved_min_of_rounds(
        [(name, (lambda eng=eng: run_open(eng))) for name, eng in modes],
        rounds=rounds, warmup=0)
    out = {}
    for name, eng in modes:
        dt = best[name] / 1e6
        gen = gens[name]
        st = eng.stats
        pct = st.ttft_percentiles()
        rec = {"op": "serve_open", "shape": shape, "schedule": name,
               "us_per_call": round(dt * 1e6, 1),
               "tok_per_s": round(gen / dt, 1),
               "arrival_rate_rps": round(float(rate), 2),
               "queue_depth_max": int(st.queue_depth_max),
               "ttft_p50_ms": round(pct.get("p50", 0.0), 2),
               "ttft_p95_ms": round(pct.get("p95", 0.0), 2),
               "prefill_ms": round(st.prefill_ms / rounds, 2),
               "chunk_ms": round(st.chunk_ms / rounds, 2),
               "decode_ms": round(st.decode_ms / rounds, 2),
               "host_ms": round(st.host_ms / rounds, 2)}
        out[name] = rec
        SERVE_RECORDS.append(rec)
        _row(f"serve_open/{name}", dt * 1e6,
             f"{gen / dt:.0f} tok/s ttft p95 {pct.get('p95', 0):.1f}ms "
             f"queue≤{st.queue_depth_max}")
        if name == "open_scheduler_v2":
            print(f"# serve_open v2 evidence: {st.early_admits} early "
                  f"admits, {st.bucket_upgrades} bucket upgrades "
                  f"({st.deferred_upgrades} deferred), "
                  f"{st.overlapped_prefills} overlapped of {st.prefills} "
                  f"prefills")
    v1, v2 = out["open_packed_overlap"], out["open_scheduler_v2"]
    _row("serve_open/v2_vs_v1_tokps",
         v2["tok_per_s"] / max(v1["tok_per_s"], 1e-9) * 100,
         f"{v2['tok_per_s'] / max(v1['tok_per_s'], 1e-9):.2f}x tok/s, "
         f"ttft_p95 {v1['ttft_p95_ms']:.1f} -> {v2['ttft_p95_ms']:.1f}ms "
         f"at {rate:.1f} req/s offered (>=1.0x tok/s and a lower p95 "
         f"expected; on a single-core host the tok/s leg is parity — "
         f"pipelined prefills only buy throughput with cores to overlap)")


def serve_cached(n_requests=24, max_new=16, slots=8):
    """Prefix/state caching on a shared-system-prompt workload: every
    request carries the same long declared prefix plus a short unique
    tail (fresh tails every round — each timed request is a real
    partial hit, not a replay). ``cache_off`` chunk-prefills the whole
    prompt cold per request; ``cache_on`` restores the prefix's O(1)
    state from the StateCache and prefills only the tail in a
    smallest-bucket slab — the headline is the TTFT p50 reduction at
    matched-or-better tok/s (the cache can only REMOVE prefill work).
    ``cache_spec`` adds speculative decode (k=4 n-gram drafts, one verify
    forward, trajectory rollback) on top: streams stay bit-identical
    (asserted against cache_on inside the run) and spec_accept_rate is
    the observable — near zero on this random-token tiny model, which is
    the honest number; the draft source only pays off on repetitive
    text."""
    rounds = 3
    prefix_len, tail = 192, 8
    if SMOKE:
        n_requests, max_new, slots, rounds = 8, 6, 4, 2
        prefix_len = 48
    print(f"# serve_cached: shared {prefix_len}-token system prompt + "
          f"{tail}-token tails, cache off vs on vs on+spec, tiny-mamba, "
          f"{n_requests} requests, {slots} slots, max_new={max_new}")
    from repro.models.lm import build_model
    from repro.launch.serve import ServeEngine
    from repro.launch.state_cache import StateCache

    cfg = _tiny_mamba()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    shared = rng.integers(1, cfg.vocab, size=prefix_len).astype(np.int32)
    max_len = prefix_len + tail + 2 * max_new + 8
    shape = (f"tiny-mamba_prefix{prefix_len}_reqs{n_requests}_"
             f"slots{slots}_new{max_new}")
    kw = dict(buckets=(32, 64, 128), max_segments=4, overlap=True,
              chunk_rows=2)
    caches = {"cache_on": StateCache(64 << 20),
              "cache_spec": StateCache(64 << 20)}
    modes = [("cache_off",
              ServeEngine(model, params, slots, max_len, **kw)),
             ("cache_on",
              ServeEngine(model, params, slots, max_len,
                          state_cache=caches["cache_on"], **kw)),
             ("cache_spec",
              ServeEngine(model, params, slots, max_len,
                          state_cache=caches["cache_spec"], spec_k=4,
                          **kw))]

    def make_tails(r):
        g = np.random.default_rng(1000 + r)
        return [g.integers(1, cfg.vocab, size=tail).astype(np.int32)
                for _ in range(n_requests)]

    outs_by_mode = {}
    rounds_seen = {name: 0 for name, _ in modes}

    def run(eng, name, declare):
        r = rounds_seen[name]
        rounds_seen[name] += 1
        prompts = [np.concatenate([shared, t]) for t in make_tails(r)]
        rids = [eng.submit(p, max_new,
                           prefix_len=prefix_len if declare else None)
                for p in prompts]
        eng.run()
        outs_by_mode.setdefault(name, {})[r] = \
            [eng.outputs[i] for i in rids]
        return sum(len(eng.outputs[i]) for i in rids)

    for name, eng in modes:          # warm-up: compiles + first capture
        run(eng, name, declare=name != "cache_off")
        eng.stats = type(eng.stats)()
        if eng.state_cache is not None:
            # keep the stored prefix but zero the hit/miss counters so the
            # recorded hit_rate covers the timed rounds only
            eng.state_cache._hits.set(0)
            eng.state_cache._misses.set(0)
    best, gens = interleaved_min_of_rounds(
        [(name, (lambda name=name, eng=eng,
                 d=(name != "cache_off"): run(eng, name, d)))
         for name, eng in modes], rounds=rounds, warmup=0)
    out = {}
    for name, eng in modes:
        dt = best[name] / 1e6
        gen = gens[name]
        st = eng.stats
        pct = st.ttft_percentiles()
        rec = {"op": "serve_cached", "shape": shape, "schedule": name,
               "us_per_call": round(dt * 1e6, 1),
               "tok_per_s": round(gen / dt, 1),
               "ttft_p50_ms": round(pct.get("p50", 0.0), 2),
               "ttft_p95_ms": round(pct.get("p95", 0.0), 2),
               "prefill_ms": round(st.prefill_ms / rounds, 2),
               "chunk_ms": round(st.chunk_ms / rounds, 2),
               "decode_ms": round(st.decode_ms / rounds, 2),
               "host_ms": round(st.host_ms / rounds, 2)}
        sc = eng.state_cache
        if sc is not None:
            rec["hit_rate"] = round(sc.hits / max(sc.lookups, 1), 3)
            rec["cache_entries"] = len(sc)
            rec["cache_mb"] = round(sc.nbytes / 2**20, 2)
        if name == "cache_spec":
            rec["spec_accept_rate"] = round(eng.spec_accept_rate, 4)
            rec["spec_rounds"] = int(eng._spec_rounds.value)
        _row(f"serve_cached/{name}", dt * 1e6,
             f"{gen / dt:.0f} tok/s ttft p50 {pct.get('p50', 0):.2f}ms"
             + (f" hit_rate {rec['hit_rate']:.2f}" if sc else ""))
        out[name] = rec
        SERVE_RECORDS.append(rec)
    # bit-identity evidence: greedy streams must not depend on the cache
    # or on speculation — same tails, same tokens, every timed round
    for r in range(rounds):
        assert outs_by_mode["cache_on"][r + 1] == \
            outs_by_mode["cache_off"][r + 1], "cache changed tokens"
        assert outs_by_mode["cache_spec"][r + 1] == \
            outs_by_mode["cache_off"][r + 1], "spec changed tokens"
    print("# serve_cached identity evidence: cache_on and cache_spec "
          "streams are token-identical to cache_off in every timed round")
    off, on = out["cache_off"], out["cache_on"]
    red = off["ttft_p50_ms"] / max(on["ttft_p50_ms"], 1e-9)
    _row("serve_cached/ttft_p50_reduction", red * 100,
         f"{red:.2f}x lower TTFT p50 with the prefix cache "
         f"({off['ttft_p50_ms']:.2f} -> {on['ttft_p50_ms']:.2f}ms) at "
         f"{on['tok_per_s'] / max(off['tok_per_s'], 1e-9):.2f}x tok/s "
         f"(>= 2x TTFT expected: the {prefix_len}-token prefix restore "
         f"replaces its chunked prefill)")


# ---------------------------------------------------------------------------
# §5 discussion — packing policies
# ---------------------------------------------------------------------------

def discussion_packing_policies():
    """Paper §5: sequential 19.1% padding, local-greedy sorted 0.41% (plus
    sort-time overhead); splitting (future work, implemented here) → ~0."""
    print("# disc: packing policies on the paper's length distribution "
          "(57..2048, mean~646), capacity 4096")
    from repro.core.packing import padding_rate, pack_with_split
    from repro.data.dataset import SyntheticCorpus
    corpus = SyntheticCorpus()
    lens = np.concatenate([corpus.lengths(s, 512)
                           for s in range(8)]).tolist()
    for policy in ("sequential", "first_fit", "sorted_greedy"):
        t0 = time.perf_counter()
        rate = padding_rate(lens, 4096, policy)
        us = (time.perf_counter() - t0) * 1e6
        ref = {"sequential": "paper 19.1%", "sorted_greedy": "paper 0.41%",
               "first_fit": "n/a"}[policy]
        _row(f"disc/{policy}", us, f"padding {rate * 100:.2f}% ({ref})")
    seqs = corpus.batch_of_sequences(0, 512)
    t0 = time.perf_counter()
    sb = pack_with_split(seqs, 4096)
    us = (time.perf_counter() - t0) * 1e6
    _row("disc/split_pack", us,
         f"padding {sb.padding_rate() * 100:.3f}% (paper future work -> 0)")
    pad_rate = 1 - np.mean(lens) / 2048
    _row("disc/pad_to_max_baseline", 0.0,
         f"padding {pad_rate * 100:.1f}% (paper 66.3%)")


# ---------------------------------------------------------------------------
# Roofline table from dry-run artifacts
# ---------------------------------------------------------------------------

def roofline_table(out_dir="experiments/dryrun"):
    print("# roof: per-cell roofline terms from the compiled dry-run "
          "(v5e: 197TF bf16, 819GB/s HBM, 50GB/s ICI)")
    if not os.path.isdir(out_dir):
        print(f"# (no {out_dir}; run `python -m repro.launch.dryrun` first)")
        return
    for fn in sorted(os.listdir(out_dir)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(out_dir, fn)) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            continue
        rl = rec["roofline"]
        t_bound = max(rl["t_compute_s"], rl["t_memory_s"],
                      rl["t_collective_s"])
        _row(f"roof/{rec['arch']}/{rec['shape']}/{rec['mesh']}",
             t_bound * 1e6,
             f"dom={rl['dominant']} comp={rl['t_compute_s'] * 1e3:.2f}ms "
             f"mem={rl['t_memory_s'] * 1e3:.2f}ms "
             f"coll={rl['t_collective_s'] * 1e3:.2f}ms "
             f"frac={rl['roofline_fraction']:.3f}")


ALL = {"fig2": fig2_ssm_operator_profile,
       "fig5": fig5_training_throughput,
       "fig6": fig6_kernel_speedup,
       "disc": discussion_packing_policies,
       "roof": roofline_table,
       "serve": serve_throughput,
       "serve_open": serve_open_loop,
       "serve_cached": serve_cached,
       "train": train_throughput}


def main() -> None:
    global OBS_TRACE
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    argv = list(sys.argv[1:])
    if "--obs-trace" in argv:
        i = argv.index("--obs-trace")
        if i + 1 >= len(argv):
            raise SystemExit("--obs-trace needs a PATH argument")
        OBS_TRACE = argv[i + 1]
        del argv[i:i + 2]
    which = argv or list(ALL)
    print("name,us_per_call,derived")
    for k in which:
        ALL[k]()
    if BENCH_RECORDS:
        # machine-readable perf trajectory, trackable across PRs
        with open(BENCH_JSON, "w") as f:
            json.dump(BENCH_RECORDS, f, indent=1)
        print(f"# wrote {len(BENCH_RECORDS)} scan records to {BENCH_JSON}")
    if SERVE_RECORDS:
        with open(SERVE_JSON, "w") as f:
            json.dump(SERVE_RECORDS, f, indent=1)
        print(f"# wrote {len(SERVE_RECORDS)} serve records to {SERVE_JSON}")
    if TRAIN_RECORDS:
        with open(TRAIN_JSON, "w") as f:
            json.dump(TRAIN_RECORDS, f, indent=1)
        print(f"# wrote {len(TRAIN_RECORDS)} train records to {TRAIN_JSON}")
    if OBS_TRACE and _OBS is not None and _OBS.enabled:
        _OBS.export(OBS_TRACE)
        print(f"# obs: wrote {len(_OBS.tracer.chrome_events())} trace "
              f"events to {OBS_TRACE} (chrome://tracing / ui.perfetto.dev)")


if __name__ == "__main__":
    main()
