"""Quickstart: pack variable-length sequences, train a small Mamba, verify
Packing–Unpacking Invariance end to end.

    PYTHONPATH=src python examples/quickstart.py

CI: `.github/workflows/ci.yml` runs `make ci` on every push — the fast
tier-1 lane (`pytest -m "not slow"`; the slow-marked engine round-trips
and grid sweeps stay in the full local `make verify`), the fault-injection
chaos lane (`make verify-faults`, a randomized-but-seeded FaultPlan —
same FAULT_CHAOS_SEED, same faults, any machine), a tiny-shape
benchmark smoke whose JSON
structure is schema-checked while its timings are never gated
(`make bench-smoke`), and the observability smoke (`make obs-smoke`:
tiny traced serve+train launcher runs, Chrome-trace structure validated
by `python -m repro.obs.check`). Benchmark baselines are refreshed with
`make bench-scan` / `make bench-serve` and promoted via
`make bench-accept` (the *.new.json staging files never get committed).
"""
import dataclasses
import sys

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, "src")

from repro.configs.base import get_config
from repro.core.packing import pack, unpack
from repro.models.lm import build_model
from repro.optim.adamw import AdamW, cosine_schedule
from repro.train.trainer import make_train_step


def main():
    # 1. a tiny Mamba (the paper's architecture family)
    #    (ssm_variant="mamba2" — or get_config("mamba2-370m") — selects the
    #    head-structured Mamba-2/SSD core instead: scalar per-head decay,
    #    whose blocked schedule runs one (T,T)·(T,dh·N) matmul per head.
    #    Everything below, packing included, works identically for both.)
    cfg = dataclasses.replace(get_config("mamba-110m"),
                              d_model=128, n_layers=4, vocab=512,
                              dtype="float32", scan_chunk=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # 2. pack variable-length sequences into one fixed buffer
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
            for n in (57, 130, 75, 98, 160)]
    pb = pack(seqs, capacity=256)
    print(f"packed {len(seqs)} seqs (lens {[len(s) for s in seqs]}) into "
          f"{pb.tokens.shape[0]} buffer(s) of 256; "
          f"padding rate {pb.padding_rate():.1%}")

    # 3. PUI check: packed forward == per-sequence forward
    batch = {"tokens": pb.tokens, "positions": pb.positions,
             "segment_ids": pb.segment_ids}
    packed_logits = model.forward(params, batch)
    per_seq = unpack(packed_logits, pb)
    worst = 0.0
    for s, lg in zip(seqs, per_seq):
        single = {"tokens": jnp.asarray(s)[None],
                  "positions": jnp.arange(len(s))[None],
                  "segment_ids": jnp.ones((1, len(s)), jnp.int32)}
        ref = model.forward(params, single)[0]
        worst = max(worst, float(jnp.abs(ref - lg).max()))
    print(f"PUI: max |packed - per-seq| logit diff = {worst:.2e}")

    # 4. a few train steps on the packed batch
    opt = AdamW(cosine_schedule(1e-3, warmup=2, total=20))
    step = jax.jit(make_train_step(model, opt))
    state = {"params": params, "opt": opt.init(params)}
    for i in range(10):
        state, metrics = step(state, batch)
        if i == 0:
            f32_first_loss = float(metrics["loss"])
        if i % 3 == 0:
            print(f"step {i}: loss {float(metrics['loss']):.4f}")

    # 4b. the production training lane (examples/train_packed.py runs all
    #     of this end to end; `python -m repro.launch.train` is the real
    #     entry):
    #     * PackingLoader streams packed (rows, seq_len) buffers as a pure
    #       function of `step` (restart replay is exact); its
    #       policy="first_fit_decreasing" cuts padding_rate vs arrival
    #       order, and data/prefetch.PrefetchLoader packs the next batches
    #       on a background thread while the device trains — memoized, so
    #       every batch stays bit-identical to the synchronous loader.
    #     * dtype="bfloat16" turns on carry-aware mixed precision: the
    #       forward/backward runs bf16 while the scan/rglru/mLSTM
    #       recurrence carries and the loss reduction stay f32 (Mamba keeps
    #       SSM carries f32 — a blanket cast diverges);
    #       param_dtype="bfloat16" additionally stores params in bf16 with
    #       f32 master weights inside AdamW, so tiny updates are never
    #       lost to bf16's 8-bit mantissa.
    #     * the Trainer logs real tok/s (segment_ids > 0) next to buffer
    #       tok/s, so padding overhead is visible per step; the gated
    #       single-vs-pad-vs-pack × f32/bf16 numbers live in
    #       BENCH_train.json (`make bench-train`).
    bf16_cfg = dataclasses.replace(cfg, dtype="bfloat16")
    bf16_model = build_model(bf16_cfg)
    bf16_step = jax.jit(make_train_step(bf16_model, opt))
    p16 = bf16_model.init(jax.random.PRNGKey(0))
    s16 = {"params": p16, "opt": opt.init(p16)}
    s16, m16 = bf16_step(s16, batch)
    print(f"bf16 lane: loss {float(m16['loss']):.4f} "
          f"(f32 step 0 was {f32_first_loss:.4f}; carries stay f32)")

    # 5. serving: the same packing trick on the inference path. The
    #    ServeEngine packs queued prompts into ONE prefill forward, hands
    #    each prompt's final recurrent state off to a decode slot
    #    (model.prefill_packed -> model.scatter_into_cache), and refills
    #    slots mid-flight as requests finish — continuous batching with a
    #    bucket-bounded number of compiled prefill shapes. Refill prefills
    #    are dispatched ASYNCHRONOUSLY (overlap=True: decode keeps stepping
    #    while the packed forward is in flight), admission is latency-aware
    #    (target_ttft_ms bounds the head-of-line wait; stats.ttft_ms /
    #    itl_ms / ttft_percentiles() expose the resulting latencies), and
    #    submit() takes per-request temperature / top_k / top_p sampled in
    #    the fused decode step (temperature=0 → exact greedy).
    #    The engine is fault-tolerant: per-request deadlines
    #    (submit(..., deadline_ms=...)), cancel(rid), overload shedding
    #    (max_queue / max_queue_age_ms → ShedError), guard=True finiteness
    #    probes that quarantine NaN/Inf slots, and snapshot()/restore()
    #    through checkpoint.CheckpointManager — each request's session is
    #    one O(1) SSM state, so a killed engine resumes every in-flight
    #    request with bit-identical remaining tokens. Failure modes are
    #    deterministically injectable via repro.faults.FaultPlan.
    #    Scheduler v2: prompts longer than the largest bucket are accepted
    #    and chunk-prefilled — fixed (chunk_rows, chunk_size) slabs resume
    #    from the carried SSM state, so long prompts never head-of-line
    #    block (max_prompt_len is the explicit bound); up to
    #    max_inflight_prefills packed prefills pipeline through the
    #    overlap window; bucket_policy="ttft" trades admit-small-early vs
    #    wait-to-fill-big on the measured TTFT; and ServeStats splits
    #    wall time into prefill_ms/chunk_ms/decode_ms/host_ms.
    #    (see examples/serve_packed.py and `python -m repro.launch.serve`)
    from repro.launch.serve import ServeEngine
    engine = ServeEngine(model, state["params"], num_slots=4, max_len=64,
                         buckets=(32,), max_segments=2,
                         overlap=True, target_ttft_ms=100.0,
                         max_inflight_prefills=2)
    for i, s in enumerate(seqs[:6]):
        engine.submit(s[:20], max_new=8,
                      temperature=0.0 if i < 3 else 0.8, top_k=16)
    outs = engine.run()
    pct = engine.stats.ttft_percentiles()
    print(f"served {len(outs)} requests "
          f"({engine.stats.generated} tokens, "
          f"{engine.stats.prefills} packed prefills, "
          f"{engine.stats.overlapped_prefills} overlapped, "
          f"{len(engine.stats.buckets)} prefill shape(s) compiled; "
          f"TTFT p50 {pct['p50']:.0f}ms incl. compiles)")

    # 6. observability (repro.obs): every engine/trainer above metered
    #    through ONE MetricsRegistry — stats objects are thin views over it
    #    — and, when you pass Obs.on(), a span tracer records per-request
    #    lifecycles (queued → prefill → decode → done, one Perfetto row per
    #    request) and per-step train spans (data wait / fused step / compile
    #    marks). Off by default and provably cheap: the disabled tracer is
    #    a no-op object, and BENCH_serve.json's obs_overhead_pct row
    #    measures the ENABLED cost (< 3% expected). From the CLIs:
    #      python -m repro.launch.serve --tiny --obs-trace trace.json
    #      python -m repro.launch.train --tiny --seq-len 2048 \
    #          --obs-trace trace.json   [--profile-dir d  # + XLA profile]
    #      python -m benchmarks.run serve train --obs-trace trace.json
    #    then open trace.json in chrome://tracing or https://ui.perfetto.dev
    #    (`make obs-smoke` runs tiny traced launcher runs and validates the
    #    trace structure via python -m repro.obs.check).
    from repro.obs import Obs
    obs = Obs.on()
    engine2 = ServeEngine(model, state["params"], num_slots=4, max_len=64,
                          buckets=(32,), max_segments=2, overlap=True,
                          obs=obs)
    for s in seqs[:3]:
        engine2.submit(s[:16], max_new=4)
    engine2.run()
    print(f"obs: {len(obs.tracer.chrome_events())} trace events, "
          f"metrics serve.generated="
          f"{obs.metrics.counter('serve.generated').value}; "
          f"timeline of req0:")
    print(obs.tracer.timeline("req0"))
    obs.close()

    # 7. prefix caching on the O(1) SSM state (docs/serving.md §4): a
    #    session's whole inference state is a few KB regardless of prompt
    #    length, so the post-prefill state of a shared system prompt can be
    #    stored ONCE in a host-side LRU (launch/state_cache.py) and every
    #    later request restores it and prefills only its own suffix — or
    #    skips the forward entirely on a whole-prompt hit. Declaring
    #    submit(..., prefix_len=N) marks the shared boundary; streams stay
    #    bit-identical to cold prefills (tests/test_state_cache.py).
    #    From the CLI: --cache-mb 64 --shared-prefix 48 [--spec-k 4].
    from repro.launch.state_cache import StateCache
    sc = StateCache(32 << 20)      # 32 MB byte budget, LRU
    system = seqs[0][:24].tolist()  # a shared "system prompt"
    warm_kw = dict(num_slots=4, max_len=96, buckets=(16, 32),
                   max_segments=2, overlap=True, chunk_rows=1,
                   chunk_size=32, state_cache=sc)
    eng_a = ServeEngine(model, state["params"], **warm_kw)
    tails8 = [rng.integers(1, cfg.vocab, size=8).tolist() for _ in range(4)]
    for t in tails8:
        eng_a.submit(system + t, max_new=4, prefix_len=len(system))
    outs_a = eng_a.run()
    # a SECOND engine reuses the same cache: every request is a warm hit
    eng_b = ServeEngine(model, state["params"], **warm_kw)
    for i, t in enumerate(tails8):
        eng_b.submit(system + t, max_new=4,
                     prefix_len=len(system), rid=100 + i)
    outs_b = eng_b.run()
    assert [outs_b[100 + i] for i in range(4)] == \
           [outs_a[i] for i in range(4)], "warm streams must equal cold"
    print(f"prefix cache: {sc!r}")
    print(f"  warm engine: {sc.hits} hits, "
          f"{eng_b.stats.prefill_tokens + eng_b.stats.chunk_tokens} prompt "
          f"tokens forwarded vs {eng_a.stats.prefill_tokens + eng_a.stats.chunk_tokens} cold "
          f"(streams bit-identical)")
    print("done.")


if __name__ == "__main__":
    main()
