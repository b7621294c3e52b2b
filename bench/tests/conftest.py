"""The harness's own tests run on the CPU at small sizes; the checkout's
root and src/ go on the path as bench/run.py puts them. ``small_run``
drives a whole run of a cell, with the look for a chip skipped, on a
small model computed in float32."""
import dataclasses
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_conf(H):
    return dict(H.load_json("configs", "mamba-110m"), d_model=32, n_layer=2,
                vocab_size=250, ssm_cfg={"d_state": 8, "d_conv": 4,
                                         "expand": 2, "dt_rank": "auto"})


def small_cell(H, name):
    """The cell ``name`` as BENCHMARK.json finds it, cut to a CPU size:
    rows of 128 tokens for training; for serving, prompts long enough to
    reach every prefill bucket and the chunk lane."""
    real = H.find_cell(name)
    if real.kind == "train":
        t = dict(real.traffic, seq_len=128, rows=2,
                 documents=dict(real.traffic["documents"], len_min=8,
                                len_max=60, mu=3.3, sigma=0.6))
    else:
        t = dict(real.traffic,
                 prompt=dict(real.traffic["prompt"], median=100, min=4,
                             max=500),
                 output=dict(real.traffic["output"], median=6, min=2, max=12),
                 engine={"num_slots": 4, "max_len": 520}, rate_rps=6.0,
                 drain_s=20, check={"min_tokens": 30, "max_requests": 4})
    return H.Cell(name, real.kind, 1, small_conf(H), t, real.end_to_end,
                  real.per_layer)


@pytest.fixture
def small_run(monkeypatch, capsys, tmp_path):
    """``small_run(name, fault, seconds, trace)`` -> the result line of a
    whole run of cell ``name`` at a small size; traces go to tmp_path."""
    from bench.lib import harness as H
    from bench.lib import model
    from bench.reference import mamba as ref
    # at this size bfloat16 rounding alone would read above the limits set
    # for the cells' sizes
    monkeypatch.setattr(ref, "LENGTH_QUANTUM", 64)
    monkeypatch.setattr(ref, "SCAN_CHUNK", 16)
    monkeypatch.setattr(ref, "LOSS_CHUNK", 32)
    program_config = model.program_config
    monkeypatch.setattr(model, "program_config", lambda conf: dataclasses.
                        replace(program_config(conf), dtype="float32"))
    monkeypatch.setattr(H, "OUT", tmp_path / "out")
    run = H.load_module(H.BENCH / "run.py")

    def go(name, fault=None, seconds="1", trace=0):
        cell = small_cell(H, name)
        monkeypatch.setattr(H, "find_cell", lambda name: cell)
        assert run.main(["--workload", name, "--seed", str(2 ** 31 + 7),
                         "--seconds", seconds, "--trace", str(trace)],
                        require_chip=False, fault=fault) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert list(out)[-1] == "checks"
        return out
    return go
