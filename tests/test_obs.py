"""Unified telemetry subsystem (repro/obs): metrics registry, span tracer,
Chrome trace export, and the instrumentation contract.

The load-bearing claims:
  * ``percentiles()`` is the repo's ONE percentile implementation and
    matches ``np.percentile(..., "linear")`` on every degenerate case
    (empty, single sample, duplicates, weighted multisets);
  * the registry is thread-safe — concurrent increments never lose counts
    (a bare ``+=`` on a Python int would);
  * spans nest correctly under a scripted clock and the exported JSON
    satisfies the Chrome trace-event schema ``obs.check`` enforces (the
    same validator ``make obs-smoke`` runs on real launcher traces);
  * the OFF state is free of observable effect: a serve engine with
    ``Obs.off()`` emits token streams bit-identical to one with
    ``Obs.on()`` — tracing may never perturb scheduling or sampling;
  * ``ServeStats`` is a thin view over the registry (one source of
    numbers), and the Trainer/PrefetchLoader meter through it.
"""
import json
import threading

import numpy as np
import pytest

from repro.obs import (NULL_TRACER, Counter, Gauge, Histogram,
                       MetricsRegistry, NullTracer, Obs, Tracer,
                       percentiles)
from repro.obs.check import check_trace


# ---------------------------------------------------------------- percentiles

def test_percentiles_empty_and_single():
    assert percentiles([]) == {}
    out = percentiles([42.0], (50, 95, 99))
    assert out == {"p50": 42.0, "p95": 42.0, "p99": 42.0}


def test_percentiles_matches_numpy_linear(rng):
    for n in (2, 3, 7, 100):
        vals = rng.normal(size=n)
        got = percentiles(vals, (0, 10, 50, 90, 95, 100))
        for p in (0, 10, 50, 90, 95, 100):
            np.testing.assert_allclose(got[f"p{p:g}"],
                                       np.percentile(vals, p), rtol=1e-12)


def test_percentiles_duplicates_match_numpy():
    vals = [3.0, 1.0, 3.0, 3.0, 2.0, 1.0]
    got = percentiles(vals, (25, 50, 75))
    for p in (25, 50, 75):
        np.testing.assert_allclose(got[f"p{p:g}"], np.percentile(vals, p))


def test_percentiles_weighted_equals_expanded_multiset():
    vals = [1.0, 5.0, 10.0]
    weights = [3, 1, 2]
    expanded = [1.0, 1.0, 1.0, 5.0, 10.0, 10.0]
    got = percentiles(vals, (50, 90, 95), weights=weights)
    for p in (50, 90, 95):
        np.testing.assert_allclose(got[f"p{p:g}"],
                                   np.percentile(expanded, p), rtol=1e-12)


def test_percentiles_zero_weights_and_validation():
    assert percentiles([1.0, 2.0], weights=[0, 0]) == {}
    with pytest.raises(ValueError):
        percentiles([1.0, 2.0], weights=[1.0])       # shape mismatch
    with pytest.raises(ValueError):
        percentiles([1.0, 2.0], weights=[1.0, -1.0])


# ------------------------------------------------------------------- registry

def test_registry_idempotent_and_kind_checked():
    m = MetricsRegistry()
    c = m.counter("a.x", help="first")
    assert m.counter("a.x") is c                     # idempotent handle
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("a.x")
    assert m.names() == ["a.x"]


def test_registry_concurrent_increments_lose_nothing():
    m = MetricsRegistry()
    c = m.counter("hot")
    g = m.gauge("warm")
    n_threads, n_inc = 8, 2000

    def work():
        for _ in range(n_inc):
            c.inc()
            g.add(2)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_inc
    assert g.value == 2 * n_threads * n_inc


def test_gauge_max_of_and_counter_set():
    g = Gauge("g")
    g.max_of(5)
    g.max_of(3)
    assert g.value == 5
    c = Counter("c")
    c.inc(7)
    c.set(0)
    assert c.value == 0


def test_histogram_summary_routes_through_percentiles():
    h = Histogram("h", buckets=(1, 2, 5, 10))
    assert h.summary() == {}                         # no observations
    for v in (0.5, 1.5, 1.5, 4.0, 20.0):             # 20 -> +inf tail
        h.observe(v)
    s = h.summary((50, 95))
    assert s["count"] == 5
    np.testing.assert_allclose(s["mean"], (0.5 + 1.5 + 1.5 + 4 + 20) / 5)
    # bucket upper bounds weighted by counts, tail reported at last bound
    expect = percentiles([1, 2, 5, 10, 10], (50, 95),
                         weights=[1, 2, 1, 0, 1])
    assert s["p50"] == expect["p50"] and s["p95"] == expect["p95"]
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(5, 1))             # not ascending


def test_prometheus_text_exposition():
    m = MetricsRegistry()
    m.counter("serve.shed", help="requests shed").inc(3)
    h = m.histogram("serve.ttft_ms", (10, 100))
    h.observe(5)
    h.observe(500)
    txt = m.prometheus_text()
    assert "# TYPE serve_shed counter" in txt
    assert "serve_shed 3" in txt
    assert 'serve_ttft_ms_bucket{le="10"} 1' in txt
    assert 'serve_ttft_ms_bucket{le="+Inf"} 2' in txt
    assert "serve_ttft_ms_count 2" in txt


# --------------------------------------------------------------------- tracer

def _scripted_clock(start=100.0, step=0.25):
    t = {"now": start}

    def clock():
        t["now"] += step
        return t["now"]

    return clock


def test_tracer_nesting_under_scripted_clock():
    tr = Tracer(clock=_scripted_clock())
    a = tr.start("outer", track="t", k=1)
    b = tr.start("inner", track="t")
    tr.finish(b)
    tr.finish(a, done=True)
    tr.instant("mark", track="t")
    evs = [e for e in tr.chrome_events() if e["ph"] != "M"]
    assert [(e["ph"], e["name"]) for e in evs] == [
        ("B", "outer"), ("B", "inner"), ("E", "inner"), ("E", "outer"),
        ("i", "mark")]
    # scripted clock: timestamps strictly increase, args ride along
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts) and len(set(ts)) == len(ts)
    assert evs[0]["args"] == {"k": 1}
    assert evs[3]["args"] == {"done": True}
    assert evs[4]["s"] == "t"                        # thread-scoped instant


def test_tracer_finish_is_tolerant_and_clamped():
    tr = Tracer(clock=_scripted_clock())
    tr.finish(None)                                  # no-op, never raises
    tr.finish(12345)                                 # unknown id ignored
    assert [e for e in tr.chrome_events() if e["ph"] != "M"] == []
    tr.complete("back", t0=2.0, t1=1.0, track="t")   # end clamps to start
    b, e = [ev for ev in tr.chrome_events() if ev["ph"] in "BE"]
    assert e["ts"] >= b["ts"]


def test_tracer_span_ctx_and_tracks():
    tr = Tracer(clock=_scripted_clock())
    with tr.span("a", track="x"):
        with tr.span("b", track="y"):                # other track: no nest
            pass
    evs = tr.chrome_events()
    tids = {e["name"]: e["tid"] for e in evs if e["ph"] == "B"}
    assert tids["a"] != tids["b"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"x", "y"}


def test_tracer_bounded_events():
    tr = Tracer(clock=_scripted_clock(), max_events=3)
    for i in range(5):
        tr.instant(f"e{i}")
    assert len(tr.chrome_events()) == 3              # incl. track metadata
    assert tr.dropped == 3
    assert tr.to_chrome()["otherData"]["dropped_events"] == 3


def test_chrome_export_schema_via_checker(tmp_path):
    obs = Obs.on(clock=_scripted_clock())
    with obs.tracer.span("serve.step", track="engine"):
        with obs.tracer.span("serve.decode_step", track="engine", step=0):
            obs.metrics.counter("serve.decode_steps").inc()
    obs.tracer.instant("shed", track="engine")
    path = tmp_path / "trace.json"
    obs.export(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert doc["metrics"]["serve.decode_steps"] == 1
    assert check_trace(str(path), require=["serve.decode_steps"]) == []
    # the checker flags real damage: drop an E and it reports imbalance
    doc["traceEvents"] = [e for e in doc["traceEvents"]
                          if not (e["ph"] == "E"
                                  and e["name"] == "serve.decode_step")]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    errs = check_trace(str(bad))
    assert any("unclosed" in e or "unbalanced" in e for e in errs)


def test_checker_rejects_misnested_spans(tmp_path):
    evs = [{"ph": "B", "name": "a", "ts": 0, "pid": 1, "tid": 0},
           {"ph": "B", "name": "b", "ts": 1, "pid": 1, "tid": 0},
           {"ph": "E", "name": "a", "ts": 2, "pid": 1, "tid": 0},
           {"ph": "E", "name": "b", "ts": 3, "pid": 1, "tid": 0}]
    p = tmp_path / "cross.json"
    p.write_text(json.dumps({"traceEvents": evs}))
    assert any("innermost" in e for e in check_trace(str(p)))


def test_timeline_text_view():
    tr = Tracer(clock=_scripted_clock())
    with tr.span("outer", track="t"):
        with tr.span("inner", track="t"):
            pass
    txt = tr.timeline("t")
    assert "-- t" in txt and "outer" in txt and "/inner" in txt
    # inner is indented one level deeper than outer
    outer_line = next(ln for ln in txt.splitlines() if ln.endswith("outer"))
    inner_line = next(ln for ln in txt.splitlines() if ln.endswith("inner"))
    assert inner_line.index("inner") > outer_line.index("outer")


def test_null_tracer_is_inert():
    nt = NULL_TRACER
    assert isinstance(nt, NullTracer) and not nt.enabled
    assert nt.start("x") is None
    nt.finish(None)
    nt.complete("x", 0, 1)
    nt.instant("x")
    nt.sync(object())                                # no jax sync attempted
    with nt.span("x"):
        pass
    assert nt.chrome_events() == []
    assert nt.timeline() == "(tracing disabled)"
    with pytest.raises(RuntimeError):
        nt.export("/dev/null")


def test_obs_bundle_on_off():
    off = Obs.off()
    assert not off.enabled and off.tracer is NULL_TRACER
    on = Obs.on(clock=_scripted_clock())
    assert on.enabled and isinstance(on.tracer, Tracer)
    # one clock drives both the registry stamp and the span timestamps
    assert on.metrics.clock is on.tracer.clock


# ------------------------------------------------- instrumentation contracts

def test_serve_stats_is_registry_view():
    from repro.launch.serve import ServeStats
    m = MetricsRegistry()
    st = ServeStats(m)
    st.shed += 1
    st.prefills += 2
    st.queue_depth_max = max(st.queue_depth_max, 7)
    st.ttft_ms.append(12.0)
    assert m.counter("serve.shed").value == 1
    assert m.counter("serve.prefills").value == 2
    assert m.gauge("serve.queue_depth_max").value == 7
    assert m.histogram("serve.ttft_ms", (1,)).count == 1
    assert st.ttft_percentiles() == {"p50": 12.0, "p95": 12.0}
    fresh = ServeStats()                             # standalone registry
    assert fresh.shed == 0 and fresh.ttft_percentiles() == {}


@pytest.mark.slow
def test_serve_disabled_obs_token_streams_bit_identical(rng):
    """Tracing may never perturb the engine: the same engine config run
    with Obs.off(), Obs.on(), and no obs argument at all produces
    bit-identical per-request token streams."""
    from repro.configs.base import get_config
    from repro.launch.serve import ServeEngine
    from repro.models.lm import build_model

    cfg = get_config("mamba-110m").reduced()
    model = build_model(cfg)
    params = model.init(__import__("jax").random.PRNGKey(0))
    prompts = [rng.integers(1, cfg.vocab, size=int(n)).astype(np.int32)
               for n in rng.integers(4, 24, size=6)]
    budgets = [int(b) for b in rng.integers(3, 8, size=6)]
    kw = dict(num_slots=3, max_len=64, buckets=(16, 32), max_segments=2,
              overlap=True)

    def run(obs):
        eng = ServeEngine(model, params, **kw) if obs is None else \
            ServeEngine(model, params, obs=obs, **kw)
        rids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        eng.run()
        return [eng.outputs[r] for r in rids], eng

    base, _ = run(None)
    off, eng_off = run(Obs.off())
    on, eng_on = run(Obs.on())
    assert base == off == on
    # and the traced engine actually recorded the lifecycle
    evs = eng_on.obs.tracer.chrome_events()
    names = {e["name"] for e in evs if e["ph"] == "B"}
    assert {"queued", "prefill", "decode", "serve.step"} <= names
    assert any(e["name"] == "first_token" for e in evs if e["ph"] == "i")
    assert eng_off.obs.tracer.chrome_events() == []


def test_trainer_metering_through_registry():
    from repro.data.dataset import SyntheticCorpus, CorpusConfig
    from repro.data.packing_loader import PackingLoader, LoaderConfig
    from repro.models.lm import build_model
    from repro.optim.adamw import AdamW, constant_schedule
    from repro.train.trainer import Trainer, TrainerConfig
    from repro.configs.base import get_config
    import jax

    cfg = get_config("mamba-110m").reduced()
    model = build_model(cfg)
    corpus = SyntheticCorpus(CorpusConfig(vocab=cfg.vocab, seed=0,
                                          len_min=4, len_max=48,
                                          mu=2.6, sigma=0.4))
    loader = PackingLoader(corpus, LoaderConfig(rows=2, seq_len=64,
                                                mode="pack"))
    obs = Obs.on()
    tr = Trainer(model, AdamW(constant_schedule(1e-3)), loader,
                 TrainerConfig(steps=3, log_every=10), obs=obs)
    _, hist = tr.train(jax.random.PRNGKey(0), verbose=False)
    assert len(hist) == 3
    m = obs.metrics
    assert m.counter("train.steps").value == 3
    assert m.counter("train.real_tokens").value == \
        sum(int(r["real_tokens"]) for r in hist)
    assert m.counter("train.compiles").value == 1    # one batch shape
    # per-step spans landed on the train track with the compile mark
    spans = [e for e in obs.tracer.chrome_events()
             if e["ph"] == "B" and e["name"] == "train.step"]
    assert len(spans) == 3
    assert spans[0]["args"]["compile"] is True
    assert spans[1]["args"]["compile"] is False


# ----------------------------------- profiler annotations, GC and scopes

class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter and exit."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler
    _FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    return _FakeAnnotation.log


def test_recording_tracer_annotates_profiled_tracks(annotations):
    tr = Tracer(clock=_scripted_clock())
    with tr.span("serve.step", track="engine"):
        sid = tr.start("serve.decode_step", track="engine")
        tr.finish(sid)
    with tr.span("train.host", track="train"):
        pass
    tr.gc_callback("start", {"generation": 0})
    tr.gc_callback("stop", {"generation": 0, "collected": 3})
    # host-only: per-request tracks, other tracks, stamped spans, instants
    with tr.span("queued", track="req0"):
        pass
    with tr.span("tune.sweep", track="tune"):
        pass
    tr.complete("serve.late", 0.0, 1.0, track="engine")
    tr.instant("cache_insert", track="engine")
    assert annotations == [
        ("enter", "serve.step"), ("enter", "serve.decode_step"),
        ("exit", "serve.decode_step"), ("exit", "serve.step"),
        ("enter", "train.host"), ("exit", "train.host"),
        ("enter", "host.gc"), ("exit", "host.gc")]
    gc_b, gc_e = [e for e in tr.chrome_events() if e["name"] == "host.gc"]
    assert gc_b["args"] == {"generation": 0}
    assert gc_e["args"] == {"collected": 3}


def test_profiled_span_names_carry_a_program_prefix(annotations):
    from repro.obs import PROFILED_TRACKS, SPAN_PREFIXES
    assert PROFILED_TRACKS == {"engine", "train", "host"}
    tr = Tracer(clock=_scripted_clock())
    for track in sorted(PROFILED_TRACKS):
        with pytest.raises(ValueError, match="must begin"):
            tr.start("decode_step", track=track)
    assert all(p.endswith(".") for p in SPAN_PREFIXES)
    assert annotations == []


def test_null_tracer_enters_no_annotation(annotations):
    with NULL_TRACER.span("serve.step", track="engine"):
        NULL_TRACER.finish(NULL_TRACER.start("train.step", track="train"))
    NULL_TRACER.gc_callback("start", {"generation": 2})
    assert annotations == []


def test_gc_hook_records_only_until_closed():
    import gc
    before = list(gc.callbacks)
    off = Obs.off()
    assert gc.callbacks == before                    # nothing recording
    obs = Obs.on()
    gc.collect()
    passes = [e for e in obs.tracer.chrome_events() if e["name"] ==
              "host.gc"]
    assert any(e["ph"] == "B" and e["args"]["generation"] == 2
               for e in passes)
    assert all("collected" in e["args"] for e in passes if e["ph"] == "E")
    obs.close()
    assert gc.callbacks == before
    n = len(obs.tracer.chrome_events())
    gc.collect()
    assert len(obs.tracer.chrome_events()) == n
    off.close()                                      # a no-op when off


SCOPES = ("mamba.norm", "mamba.in_proj", "mamba.conv", "mamba.x_proj",
          "mamba.scan", "mamba.out_proj", "lm.embed", "lm.head")


@pytest.fixture(scope="module")
def tiny_mamba():
    import jax
    from repro.configs.base import get_config
    from repro.models.lm import build_model
    model = build_model(get_config("mamba-110m").reduced())
    return model, model.init(jax.random.PRNGKey(0))


def test_train_step_and_prefill_carry_named_scopes(tiny_mamba):
    """Each scope reaches the compiled HLO's op metadata, which is what a
    device trace's ops are named by; the packed prefill jits under its own
    name."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import ServeEngine
    from repro.optim.adamw import AdamW, constant_schedule
    from repro.train.trainer import make_train_step

    model, params = tiny_mamba
    opt = AdamW(constant_schedule(1e-3))
    batch = {k: jnp.ones((2, 64), jnp.int32)
             for k in ("tokens", "positions", "segment_ids")}
    state = {"params": params, "opt": opt.init(params)}
    train = jax.jit(make_train_step(model, opt)).lower(state, batch)
    text = train.compile().as_text()
    for scope in SCOPES + ("optim.adamw",):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    eng = ServeEngine(model, params, num_slots=2, max_len=128,
                      buckets=(64,))
    ends = jnp.full((eng.prefill_rows, eng.max_segments), -1, jnp.int32)
    pre = eng._prefill.lower(params, batch, ends=ends)
    text = pre.compile().as_text()
    assert text.startswith("HloModule jit_prefill_packed")
    for scope in SCOPES:
        assert f"/{scope}/" in text or f"({scope})" in text, scope
