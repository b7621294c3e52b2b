"""The harness is driven by data: BENCHMARK.json names every file it needs,
and a configuration, a traffic mix, a cell or a per-layer metric is added
by adding files only."""
import json
import re
import shutil

from pathlib import Path

import pytest

from bench.lib import harness as H
from bench.lib import xtrace

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RUN_PY = Path(__file__).resolve().parents[1] / "run.py"
FIXTURE = Path(__file__).parent / "data" / "fixture.xplane.pb"
KINDS = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return H.load_benchmark()


def test_benchmark_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    assert len({m["name"] for k in ("end_to_end", "per_layer")
                for m in bench[k]}) == len(bench["end_to_end"]) + \
        len(bench["per_layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in KINDS
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_named_file_exists(bench):
    for c in bench["configs"]:
        assert (H.ROOT / c["file"]).is_file()
        assert json.load(open(H.ROOT / c["file"]))["name"] == c["name"]
    for w in bench["workloads"]:
        assert (H.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (H.BENCH / "workloads" / f"{w['name']}.json").is_file()
        cell = H.find_cell(w["name"], bench)
        assert (H.BENCH / "drivers" / f"{cell.kind}.py").is_file()
    for m in bench["per_layer"]:
        assert (H.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = H.find_cell(w["name"], bench)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in reported and m["moves"] in e2e


def _tiny_tree(tmp_path):
    """A copy of the benchmark's files with one more cell, configuration,
    traffic mix and two per-layer metrics, each a new file: one reads a
    counter of the program's registry, one its host spans."""
    root = tmp_path / "checkout"
    shutil.copytree(H.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = H.load_benchmark()
    conf = json.load(open(H.BENCH / "configs" / "mamba-110m.json"))
    conf["name"] = "mamba-new"
    (root / "bench" / "configs" / "mamba-new.json").write_text(
        json.dumps(conf))
    mix = dict(H.load_json("traffic", "pack4k"), seq_len=8192)
    (root / "bench" / "traffic" / "newmix.json").write_text(json.dumps(mix))
    (root / "bench" / "workloads" / "train.mamba-new.newmix.json"
     ).write_text(json.dumps(H.load_json("workloads",
                                         "train.mamba-110m.pack4k")))
    (root / "bench" / "metrics" / "train.new_steps.py").write_text(
        "from bench.lib.harness import counter_delta\n\n\n"
        "def read(run):\n"
        "    return counter_delta(run, 'train.steps') or None\n")
    (root / "bench" / "metrics" / "train.new_step_ms.py").write_text(
        "from bench.lib.spans import durations_ms\n\n\n"
        "def read(run):\n"
        "    d = durations_ms(run['spans'], 'train.step',\n"
        "                     run['window_t0_us'], run['window_t1_us'])\n"
        "    return sum(d) / len(d) if d else None\n")
    bench["configs"].append({"name": "mamba-new", "source": "x",
                             "file": "bench/configs/mamba-new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "train.mamba-new.newmix",
                               "config": "mamba-new", "traffic": "newmix",
                               "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("train.mamba-new.newmix")
    for name, source in (("train.new_steps", "program_counter"),
                         ("train.new_step_ms", "program_span")):
        bench["per_layer"].append({"name": name, "unit": "1",
                                   "better": "higher", "source": source,
                                   "layer": "train step",
                                   "moves": "train_tokens_per_s",
                                   "workloads": ["train.mamba-new.newmix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    root = _tiny_tree(tmp_path)
    monkeypatch.setattr(H, "ROOT", root)
    monkeypatch.setattr(H, "BENCH", root / "bench")
    cell = H.find_cell("train.mamba-new.newmix")
    assert cell.kind == "train" and cell.config["name"] == "mamba-new"
    assert cell.traffic["seq_len"] == 8192 and cell.traffic["rows"] == 8
    assert [m["name"] for m in cell.per_layer] == ["train.new_steps",
                                                   "train.new_step_ms"]
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s",
                                                    "setup_s"}


def test_new_metric_files_read_a_real_run(tmp_path, monkeypatch, small_run):
    """A traced run of the new cell through bench/run.py and the train
    driver as they are: the new readers find the program's counters and
    spans of the window. The CPU has no device plane, so the committed
    TPU trace stands in for the profiler's."""
    root = _tiny_tree(tmp_path)
    monkeypatch.setattr(H, "ROOT", root)
    monkeypatch.setattr(H, "BENCH", root / "bench")
    summarize = xtrace.summarize
    monkeypatch.setattr(xtrace, "summarize",
                        lambda path: summarize(FIXTURE))
    out = small_run("train.mamba-new.newmix", trace=1)
    assert out["correct"] is True, out["checks"]
    m = out["metrics"]
    assert set(m) == {"train.new_steps", "train.new_step_ms"}
    assert m["train.new_steps"]["value"] == out["attempted"] > 0
    assert m["train.new_step_ms"]["value"] > 0
    assert out["device"]["busy_s"] > 0 and out["breakdown"]["device_ops"]


def test_a_reader_that_finds_nothing_is_left_out(monkeypatch):
    run = H.load_module(RUN_PY)
    cell = H.find_cell("train.mamba-110m.pack4k")
    data = {"registry_t0": {}, "registry_t1": {}, "spans": [],
            "window_t0_us": 0.0, "window_t1_us": 1.0, "window_s": 1.0,
            "chips": 1, "device_kind": "TPU v5 lite",
            "config": cell.config}
    res = H.Result(attempted=0, failed=0, end_to_end={}, checks=[],
                   device={}, layer_data=data)
    assert run.per_layer_metrics(cell, res, None) == {}


def test_unknown_device_kind_is_an_error():
    assert H.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        H.peaks("cpu")
