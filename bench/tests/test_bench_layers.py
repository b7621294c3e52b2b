"""The readings by the program's own names (bench/lib/layers.py) and the
readers built on them. Most tests read a small trace recorded on a TPU v5e
by bench/tools/record_layer_fixture.py: per round, a ``train.step`` span
over a program whose ``mamba.scan`` scope does three times the matmuls of
its ``mamba.conv`` scope, which does twice those of ``mamba.in_proj``;
then 20 ms idle in ``serve.decode_emit`` inside
``serve.step`` (10 ms more) inside ``bench.engine_step`` (5 ms more); then
a ``train.host`` span that makes cyclic garbage, collects it in one GC
pass, and pauses 5 ms."""
import json
import shutil
from pathlib import Path

import pytest

from bench.lib import harness as H
from bench.lib import layers, xtrace

DATA = Path(__file__).parent / "data"
FIXTURE = DATA / "layers.xplane.pb"
SPANS = DATA / "layers.spans.json"
OLD_FIXTURE = DATA / "fixture.xplane.pb"
RUN_PY = Path(__file__).resolve().parents[1] / "run.py"
NEW = {"train": {"train.scan_ms", "train.conv_ms", "train.proj_ms"},
       "serve": {"serve.host_idle_ms", "serve.step_max_ms"}}


def test_innermost_cuts_at_every_edge():
    spans = [(0, 10, "outer"), (2, 6, "inner"), (4, 5, "gc")]
    assert layers.innermost(spans, 1, 8) == [
        (1, 2, ("outer",)), (2, 4, ("outer", "inner")),
        (4, 5, ("outer", "inner", "gc")), (5, 6, ("outer", "inner")),
        (6, 8, ("outer",))]
    # of two spans that start together, the shorter is the inner one
    assert layers.innermost([(0, 4, "b"), (0, 9, "a")], 0, 2) == [
        (0, 2, ("a", "b"))]
    assert layers.innermost([], 3, 4) == [(3, 4, ())]


def test_scope_of_takes_the_innermost_program_scope():
    assert layers.scope_of("jit(step_fn)/transpose(jvp())/while/body/"
                           "closed_call/checkpoint/mamba.scan/mul") == \
        "mamba.scan"
    assert layers.scope_of("jit(step_fn)/transpose(jvp(lm.head))/dot") \
        == "lm.head"
    assert layers.scope_of("jit(f)/lm.embed/mamba.conv/add") == "mamba.conv"
    assert layers.scope_of("jit(f)/while/body/dynamic_slice") == \
        layers.UNSCOPED
    assert layers.scope_of(None) == layers.UNSCOPED


def test_trace_without_program_spans_reads_as_xtrace_does():
    """A trace of a program without spans or scopes: the idle split is
    xtrace's, every op is unscoped, and the readers read nothing."""
    got = layers.reduce(OLD_FIXTURE)
    want = xtrace.summarize(OLD_FIXTURE)
    assert got.window_s == want.window_s
    assert got.idle_by_span == pytest.approx(want.idle_by_host, abs=1e-12)
    assert set(got.scope_s) == {layers.UNSCOPED}
    assert got.scope_s[layers.UNSCOPED] == pytest.approx(
        want.busy_s, rel=0.01)
    meta = layers.op_metadata(OLD_FIXTURE)
    assert meta and all(v.startswith("jit(<lambda>)/") for v in
                        meta.values())


@pytest.fixture(scope="module")
def reading():
    return layers.reduce(FIXTURE)


def test_gaps_go_to_the_innermost_span(reading):
    idle = reading.idle_by_span
    assert idle["serve.decode_emit"] == pytest.approx(0.060, abs=0.006)
    assert idle["serve.step"] == pytest.approx(0.030, abs=0.006)
    assert idle["bench.engine_step"] == pytest.approx(0.015, abs=0.006)
    # making the garbage (in train.host) and collecting it (host.gc) take
    # tens of milliseconds a round besides the 5 ms pause
    assert idle["train.host"] > 0.015 and idle["host.gc"] > 0.003
    assert reading.span_counts["host.gc"] == 3
    engine = idle["serve.decode_emit"] + idle["serve.step"] + \
        idle["bench.engine_step"]
    assert engine <= reading.idle_under["bench.engine_step"] < \
        engine + 0.002
    want = xtrace.summarize(FIXTURE)
    assert sum(idle.values()) == pytest.approx(
        want.window_s - want.busy_s, rel=1e-9)
    assert reading.span_counts["serve.step"] == 3
    assert reading.span_counts["train.step"] == 3


def test_device_time_by_scope(reading):
    s = reading.scope_s
    assert 2.4 < s["mamba.scan"] / s["mamba.conv"] < 3.6
    assert 1.6 < s["mamba.conv"] / s["mamba.in_proj"] < 2.4
    assert s.get(layers.UNSCOPED, 0) < 0.1 * sum(s.values())
    assert all(n.split("/")[0] in ("mamba.scan", "mamba.conv")
               for n, _ in reading.top_ops[:8])


def _data(cell, tmp_path, monkeypatch):
    """What a driver hands the readers, with the fixture as the trace the
    run left under the benchmark's output directory."""
    out = tmp_path / "out"
    dst = out / cell.name / "trace-1" / "plugins" / "profile" / "t"
    dst.mkdir(parents=True)
    shutil.copy(FIXTURE, dst / "host.xplane.pb")
    monkeypatch.setattr(H, "OUT", out)
    spans = json.loads(SPANS.read_text())
    return dict(spans, window_s=1.0, chips=1, device_kind="TPU v5 lite",
                config=cell.config, registry_t0={"train.steps": 0},
                registry_t1={"train.steps": 3})


@pytest.mark.parametrize("kind, cell", [
    ("train", "train.mamba-110m.pack4k"),
    ("train", "train.mamba-1.4b-s12.pack4k"),
    ("serve", "serve.mamba-110m.chat")])
def test_every_new_reader_reads_the_fixture(kind, cell, tmp_path,
                                            monkeypatch, capsys):
    run = H.load_module(RUN_PY)
    c = H.find_cell(cell)
    assert NEW[kind] <= {m["name"] for m in c.per_layer}
    res = H.Result(attempted=0, failed=0, end_to_end={}, checks=[],
                   device={}, layer_data=_data(c, tmp_path, monkeypatch))
    got = run.per_layer_metrics(c, res, xtrace.summarize(FIXTURE))
    assert NEW[kind] <= set(got)
    v = {k: got[k]["value"] for k in NEW[kind]}
    if kind == "train":
        scan = layers.reduce(FIXTURE).scope_s["mamba.scan"]
        assert v["train.scan_ms"] == pytest.approx(1e3 * scan / 3)
        assert 2.4 < v["train.scan_ms"] / v["train.conv_ms"] < 3.6
        assert 1.6 < v["train.conv_ms"] / v["train.proj_ms"] < 2.4
    else:
        # 30 ms of engine idle per round, one serve.step per round
        assert v["serve.host_idle_ms"] == pytest.approx(30, abs=3)
        assert v["serve.step_max_ms"] >= 30
        err = capsys.readouterr().err
        assert "in the engine's spans" in err
        assert "serve.decode_emit=" in err


def test_readers_read_nothing_without_a_trace():
    run = H.load_module(RUN_PY)
    for name in ("train.mamba-110m.pack4k", "serve.mamba-110m.chat"):
        cell = H.find_cell(name)
        data = {"registry_t0": {}, "registry_t1": {}, "spans": [],
                "window_t0_us": 0.0, "window_t1_us": 1.0, "window_s": 1.0,
                "chips": 1, "device_kind": "TPU v5 lite",
                "config": cell.config}
        res = H.Result(attempted=0, failed=0, end_to_end={}, checks=[],
                       device={}, layer_data=data)
        assert run.per_layer_metrics(cell, res, None) == {}


def test_traced_serve_run_reads_its_longest_engine_step(small_run,
                                                        monkeypatch):
    """A traced small serve run through bench/run.py and the serve driver
    as they are: the engine's own spans give the longest step. The CPU
    has no device plane, so the committed TPU trace stands in for the
    profiler's summary, and the device readers find no trace of theirs."""
    summarize = xtrace.summarize
    monkeypatch.setattr(xtrace, "summarize",
                        lambda path: summarize(OLD_FIXTURE))
    out = small_run("serve.mamba-110m.chat", trace=1)
    assert out["correct"] is True, out["checks"]
    m = out["metrics"]
    assert m["serve.step_max_ms"]["value"] > 0
    assert "serve.host_idle_ms" not in m
