"""Driver of the ``serve`` cells: open-loop traffic into the program's
``ServeEngine``, driven through ``ServeEngine.step`` from one thread.

Set-up makes the weights on the device from the seed, builds the engine
with the mix's slots and slot length (everything else at the engine's
defaults), and serves one warm-up prompt for each shape the engine says
it compiles: each prefill bucket, and each slab width of the chunk lane.
Compilations in the window are counted and reported.

The window submits each request when it is due and steps the engine while
it has work. A request's time to first token runs from when it was due to
when its first token is seen after a step; its time per output token is
(last token - first token) / (tokens - 1). Requests due in the window are
counted; arrivals go on after the window closes until each of them has
finished, for at most ``drain_s``. A request that never finishes is
failed, and its latencies are taken at the drain's end.

Correctness: a sample, drawn from the seed, of the finished requests, the
longest among them; the plain reference runs once over each prompt with
its served tokens, and the number compared is the widest gap by which a
served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from bench.lib import harness as H
from bench.lib.model import init_weights, jax_key, program_config, sizes, \
    to_program, from_program, check_layout
from bench.lib import xtrace
from bench.lib.traffic import chat_requests
from bench.reference import mamba as ref


def build_engine(conf: dict, traffic: dict, params, obs=None):
    from repro.launch.serve import ServeEngine
    from repro.models.lm import build_model
    model = build_model(program_config(conf))
    e = traffic["engine"]
    return ServeEngine(model, params, num_slots=e["num_slots"],
                       max_len=e["max_len"], obs=obs), model


def make_params(conf: dict):
    """The served weights: one jitted call from the key."""
    import jax
    s = sizes(conf)
    return jax.jit(lambda k: to_program(init_weights(s, k)))


def served_gaps(w, conf, prompts, outs, prec: str = "f32") -> List[float]:
    """Per request, the widest gap between the reference's best logit and
    its logit of the served token (``prec`` = "fp8": the token the control
    puts first instead of the served one)."""
    eps = sizes(conf).eps
    seqs = [np.concatenate([p, o[:-1]]).astype(np.int32)
            for p, o in zip(prompts, outs)]
    starts = [len(p) - 1 for p in prompts]
    want = ref.served_logits(w, seqs, starts, eps, "f32")
    if prec != "f32":
        ctl = ref.served_logits(w, seqs, starts, eps, prec)
        outs = [c.argmax(-1) for c in ctl]
    return [float(np.max(lg.max(-1) - lg[np.arange(len(o)), o]))
            for lg, o in zip(want, outs)]


def sample(ids: List[int], total: Dict[int, int], served: Dict[int, int],
           seed: int, min_tokens: int, max_requests: int) -> List[int]:
    """The longest request (prompt and answer), then others in an order
    drawn from the seed, until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 4]))
    longest = max(ids, key=lambda i: total[i])
    out = [longest]
    tokens = served[longest]
    for i in rng.permutation(ids):
        if tokens >= min_tokens or len(out) >= max_requests:
            break
        if i != longest:
            out.append(int(i))
            tokens += served[i]
    return out


def warm_up_lengths(eng) -> List[int]:
    """One prompt length per shape the engine compiles, from its own sizes:
    a prompt that fills each prefill bucket, and for the chunk lane one
    full slab followed by a last slab of each width that
    ``packing.slab_width`` can pick (the buckets up to the slab, and the
    slab). Lengths that no slot can hold are left out: no request of them
    can come."""
    lengths = list(eng.buckets)
    if eng.chunk_enabled:
        widths = {b for b in eng.buckets if b <= eng.chunk_size}
        lengths += [eng.chunk_size + w
                    for w in sorted(widths | {eng.chunk_size})]
    return [n for n in lengths if n + 2 <= eng.max_len]


def _warm_up(eng, vocab: int):
    rng = np.random.default_rng(0)
    for n in warm_up_lengths(eng):
        eng.submit(rng.integers(1, vocab, size=n, dtype=np.int32), 2)
        eng.run()


def run(cell: H.Cell, seed: int, seconds: float, trace: bool,
        clock: H.SetupClock, devs, fault: Optional[str] = None) -> H.Result:
    import jax
    from repro.obs import Obs

    t, conf = cell.traffic, cell.config
    s = sizes(conf)
    key = jax_key(seed, 1)
    make = make_params(conf)
    params = make(key)
    jax.block_until_ready(params)
    clock.mark("init_weights")
    obs = Obs.on() if trace else Obs.off()
    eng, model = build_engine(conf, t, params, obs=obs)
    check_layout(model, s)
    if fault == "altered_token":
        finish = eng._finish_token
        eng._finish_token = lambda slot, tok: finish(
            slot, (tok + 1) % s.vocab_real if tok % 5 == 0 else tok)
    clock.mark("build")
    _warm_up(eng, s.vocab_real)
    clock.mark("warm_up")

    rate = t["rate_rps"]
    reqs = chat_requests(t, seed, s.vocab_real, rate, seconds + t["drain_s"])
    n = len(reqs)
    in_window = [i for i, r in enumerate(reqs) if r.due_s < seconds]
    compiles = H.CompileCounter()
    trace_dir = None
    if trace:
        trace_dir = cell.out_dir / f"trace-{seed}"
        trace_dir.mkdir(parents=True, exist_ok=True)
        xtrace.start(trace_dir)
    window_ann = jax.profiler.TraceAnnotation("bench.window")
    window_ann.__enter__()
    jax.config.update("jax_log_compiles", True)   # names any compile below
    registry_t0 = obs.metrics.to_dict()
    compiles.on = True
    t0 = time.perf_counter()
    setup_s = clock.total(t0)

    pending = collections.deque(range(n))
    live: Dict[int, int] = {}          # rid -> request index
    rid_of: Dict[int, int] = {}        # request index -> rid
    first: Dict[int, float] = {}
    done: Dict[int, float] = {}
    late: List[float] = []
    open_ = set(in_window)
    deadline = seconds + t["drain_s"]
    window_closed = None
    while True:
        now = time.perf_counter() - t0
        if window_closed is None and now >= seconds:
            window_closed = now
            compiles.on = False
            registry_t1 = obs.metrics.to_dict()
            window_ann.__exit__(None, None, None)
            jax.config.update("jax_log_compiles", False)
        with jax.profiler.TraceAnnotation("bench.submit"):
            while pending and reqs[pending[0]].due_s <= now:
                i = pending.popleft()
                r = reqs[i]
                rid_of[i] = eng.submit(r.prompt, r.max_new)
                live[rid_of[i]] = i
                late.append(now - r.due_s)
        if (now >= seconds and not open_) or now >= deadline:
            break
        if live:
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                eng.step()
            seen = time.perf_counter() - t0
            for rid, i in list(live.items()):
                if i not in first and eng.outputs[rid]:
                    first[i] = seen
                st = eng.status[rid]
                if st == "done":
                    done[i] = seen
                if st not in ("queued", "active"):
                    del live[rid]
                    open_.discard(i)
        elif pending:
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(reqs[pending[0]].due_s - now,
                                        0.002)))
    end = time.perf_counter() - t0
    if trace:      # writing the trace takes seconds: not inside the loop
        jax.profiler.stop_trace()
    device = H.device_info(devs)
    outputs = {i: eng.outputs[rid] for i, rid in rid_of.items()}
    n_prompt_tokens = sum(len(reqs[i].prompt) for i in in_window)

    ttft, tpot = [], []
    never = 0
    for i in in_window:
        if i not in done:
            never += 1
        f = first.get(i, end)
        ttft.append((f - reqs[i].due_s) * 1e3)
        k = len(outputs.get(i, []))
        if k >= 2 and i in done:
            tpot.append((done[i] - f) / (k - 1) * 1e3)
    lat = np.asarray(late) * 1e3
    H.note(f"window: {len(in_window)} requests due in {seconds} s at "
           f"{rate} req/s ({n_prompt_tokens} prompt tokens), "
           f"{len(done)} finished in {end:.2f} s; {never} never finished; "
           f"{compiles.n} compilations in the window")
    H.note(f"generator lateness ms: p50 {np.percentile(lat, 50):.3f} "
           f"p99 {np.percentile(lat, 99):.3f} max {lat.max():.3f}")
    st = eng.stats
    H.note(f"engine: {st.prefills} prefills, {st.chunk_rounds} chunk "
           f"rounds, {st.decode_steps} decode steps, {st.generated} tokens")
    layer_data = H.window_data(t0, t0 + window_closed, devs, conf, obs,
                               registry_t0, registry_t1)

    del eng, params
    gc.collect()
    finished = [i for i in in_window if i in done]
    pick = sample(finished,
                  {i: len(reqs[i].prompt) + len(outputs[i]) for i in finished},
                  {i: len(outputs[i]) for i in finished}, seed,
                  t["check"]["min_tokens"], t["check"]["max_requests"])
    t_ref = time.perf_counter()
    w = from_program(make(key))
    gaps = served_gaps(w, conf, [reqs[i].prompt for i in pick],
                       [np.asarray(outputs[i]) for i in pick])
    H.note(f"reference: {len(pick)} requests, "
           f"{sum(len(outputs[i]) for i in pick)} served tokens, "
           f"{time.perf_counter() - t_ref:.1f} s")
    if fault == "control_fp8":
        # the reference in float8 put in the program's place: the token it
        # puts first at each position of the same prompts and tokens
        ctl = served_gaps(w, conf, [reqs[i].prompt for i in pick],
                          [np.asarray(outputs[i]) for i in pick], prec="fp8")
        H.note(f"reading program served_gap={max(gaps)!r} "
               f"control_fp8 served_gap={max(ctl)!r}")
        gaps = ctl
    checks = [H.Check("served_gap", max(gaps), t["limits"]["served_gap"]),
              H.Check("never_finished", float(never), 0.0)]
    return H.Result(
        attempted=len(in_window), failed=never,
        end_to_end={"serve_ttft_p95_ms": H.percentile(ttft, 95),
                    "serve_tpot_p95_ms": H.percentile(tpot, 95),
                    "setup_s": setup_s},
        checks=checks, device=device, layer_data=layer_data,
        trace_dir=trace_dir, compiles_in_window=compiles.n)

