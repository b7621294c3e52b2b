"""benchmarks/compare.py CLI contracts: the --accept baseline promotion
(staging .new.json → committed baseline, staging file removed), the
--schema structural check the CI bench smoke gates on, and one report of
every offender; benchmarks/timing.py's interleaved min-of-rounds."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
COMPARE = os.path.join(REPO, "benchmarks", "compare.py")


def _run(*args):
    return subprocess.run([sys.executable, COMPARE, *args],
                          capture_output=True, text=True)


def _rec(schedule, us, **extra):
    return {"op": "serve", "shape": "s1", "schedule": schedule,
            "us_per_call": us, "tok_per_s": 1e6 / us, **extra}


def _write(path, recs):
    with open(path, "w") as f:
        json.dump(recs, f)


def test_accept_promotes_and_removes_staging(tmp_path):
    old = tmp_path / "BENCH_x.json"
    new = tmp_path / "BENCH_x.new.json"
    _write(old, [_rec("a", 100.0)])
    staged = [_rec("a", 250.0)]              # a >10% regression, on purpose
    _write(new, staged)
    r = _run("--pair", str(old), str(new), "--accept")
    # accepting is the operator's call: regressions are SHOWN, not fatal
    assert r.returncode == 0, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout and "accepted" in r.stdout
    assert not new.exists()                  # staging file cleaned up
    assert json.load(open(old)) == staged    # baseline replaced


def test_accept_first_baseline_and_optional_missing(tmp_path):
    old = tmp_path / "BENCH_y.json"
    new = tmp_path / "BENCH_y.new.json"
    _write(new, [_rec("a", 10.0)])
    r = _run("--pair", str(old), str(new),
             "--optional-pair", str(tmp_path / "no.json"),
             str(tmp_path / "no.new.json"), "--accept")
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.load(open(old)) == [_rec("a", 10.0)]
    assert not new.exists()
    assert "skipping accept" in r.stdout
    # a REQUIRED pair with no staging file still fails the accept run
    r = _run("--pair", str(old), str(new), "--accept")
    assert r.returncode == 1
    assert "MISSING staging" in r.stdout


def test_compare_without_accept_still_gates(tmp_path):
    old = tmp_path / "BENCH_z.json"
    new = tmp_path / "BENCH_z.new.json"
    _write(old, [_rec("a", 100.0)])
    _write(new, [_rec("a", 250.0)])
    r = _run("--pair", str(old), str(new))
    assert r.returncode == 1                 # the plain gate still fails
    assert new.exists() and "REGRESSION" in r.stdout


def test_schema_ok_and_violations(tmp_path):
    good = tmp_path / "good.json"
    _write(good, [_rec("a", 10.0, ttft_p50_ms=1.5, ttft_p95_ms=9.0),
                  _rec("b", 20.0)])
    r = _run("--schema", str(good))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK, 2 records" in r.stdout

    bad = tmp_path / "bad.json"
    _write(bad, [
        {"op": "serve", "shape": "s1"},                       # missing keys
        _rec("a", -5.0),                                      # bad number
        _rec("c", 10.0, ttft_p50_ms="fast"),                  # bad ttft type
        _rec("d", 10.0), _rec("d", 11.0),                     # duplicate row
    ])
    r = _run("--schema", str(bad))
    assert r.returncode == 1
    for frag in ("schedule", "us_per_call", "ttft_p50_ms", "duplicate"):
        assert frag in r.stdout, f"{frag} not reported:\n{r.stdout}"

    empty = tmp_path / "empty.json"
    _write(empty, [])
    assert _run("--schema", str(empty)).returncode == 1
    assert _run("--schema", str(tmp_path / "missing.json")).returncode == 1


def test_interleaved_min_of_rounds_injectable_clock():
    from benchmarks.timing import interleaved_min_of_rounds
    t = [0.0]
    # fake clock: "a" costs 10us, "b" costs 5us, with a drifty round
    costs = iter([10e-6, 5e-6, 30e-6, 25e-6, 10e-6, 5e-6])

    def clock():
        return t[0]

    calls = {"a": 0, "b": 0}

    def mk(name):
        def thunk():
            calls[name] += 1
            t[0] += next(costs)
            return name
        return thunk

    best, last = interleaved_min_of_rounds(
        [("a", mk("a")), ("b", mk("b"))], rounds=3, warmup=0,
        clock=clock, sync=lambda x: x)
    assert calls == {"a": 3, "b": 3}
    assert best["a"] == pytest.approx(10.0)       # min over rounds, in us
    assert best["b"] == pytest.approx(5.0)
    assert last == {"a": "a", "b": "b"}


def test_compare_reports_all_offenders(tmp_path):
    import benchmarks.compare as cmp
    old = [{"op": "s", "shape": "a", "schedule": "x", "us_per_call": 100.0,
            "tok_per_s": 1},
           {"op": "s", "shape": "a", "schedule": "y", "us_per_call": 100.0,
            "tok_per_s": 1},
           {"op": "s", "shape": "a", "schedule": "z", "us_per_call": 100.0,
            "tok_per_s": 1}]
    new = [dict(r, us_per_call=us) for r, us in
           zip(old, (150.0, 95.0, 200.0))]
    po, pn = str(tmp_path / "o.json"), str(tmp_path / "n.json")
    json.dump(old, open(po, "w"))
    json.dump(new, open(pn, "w"))
    lines, offenders = cmp.compare(po, pn, pct=10.0)
    # BOTH regressions reported in one pass, plus the header + ok row
    assert len(offenders) == 2
    assert {o[0] for o in offenders} == {"s/a/x", "s/a/z"}
    assert any("ok" in ln and "s/a/y" in ln for ln in lines)


def test_compare_missing_required_still_reports_other_pairs(tmp_path,
                                                            capsys,
                                                            monkeypatch):
    """A missing required pair fails the gate but must not hide offenders
    in the remaining pairs (one run surfaces everything)."""
    import benchmarks.compare as cmp
    row = {"op": "s", "shape": "a", "schedule": "x", "tok_per_s": 1}
    po, pn = str(tmp_path / "o.json"), str(tmp_path / "n.json")
    json.dump([dict(row, us_per_call=100.0)], open(po, "w"))
    json.dump([dict(row, us_per_call=200.0)], open(pn, "w"))
    absent = str(tmp_path / "absent.json")
    monkeypatch.setattr("sys.argv", ["compare.py", "--pair", absent, absent,
                                     "--pair", po, pn])
    with pytest.raises(SystemExit) as e:
        cmp.main()
    assert e.value.code == 1
    out = capsys.readouterr().out
    assert "MISSING required" in out
    assert "s/a/x" in out and "+100.0%" in out     # 2nd pair still compared
