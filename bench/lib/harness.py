"""What the entry point and the drivers share: finding a cell's files by
the names in BENCHMARK.json, the set-up clock, the device and its peaks,
and the statistics of the end-to-end metrics."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
OUT = ROOT / "experiments" / "bench"
COMPILE_CACHE = ROOT / ".jax_cache"


def load_json(sub: str, name: str) -> dict:
    with open(BENCH / sub / f"{name}.json") as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    kind: str                 # runs it: bench/drivers/<kind>.py
    chips: int
    config: dict              # bench/configs/<config>.json
    traffic: dict             # bench/traffic/<traffic>.json, then the
    #                           cell's own bench/workloads/<cell>.json
    end_to_end: List[dict]    # BENCHMARK.json entries this cell reports
    per_layer: List[dict]

    @property
    def out_dir(self) -> Path:
        return OUT / self.name


def find_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    ws = [w for w in bench["workloads"] if w["name"] == name]
    if not ws:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = ws[0]
    traffic = {**load_json("traffic", w["traffic"]),
               **load_json("workloads", name)}
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name=name, kind=traffic["kind"], chips=w["chips"],
                config=load_json("configs", w["config"]), traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


class SetupClock:
    """Set-up pieces, each the time since the previous mark; ``T0`` is
    the process's first statement (bench/run.py)."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.last = t0
        self.pieces: Dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.pieces[name] = self.pieces.get(name, 0.0) + now - self.last
        self.last = now

    def total(self, at: float) -> float:
        return at - self.t0


def note(msg: str) -> None:
    """A line for the record, on standard error, before the result."""
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def peaks(kind: str) -> dict:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json; "
                       f"add its published peaks before measuring on it")
    return table[kind]


class NoChip(RuntimeError):
    pass


def require_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[:chips]


def device_info(devs) -> Dict[str, Any]:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """XLA programs built while it is on: each compilation, or load from
    the persistent cache, of a program the process has not run yet."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.n += 1


def window_data(t0: float, t1: float, devs, config: dict, obs,
                registry_t0: dict, registry_t1: dict) -> Dict[str, Any]:
    """A driver's ``Result.layer_data``: the same keys for every kind."""
    return {"window_s": t1 - t0, "chips": len(devs),
            "device_kind": devs[0].device_kind, "config": config,
            "window_t0_us": t0 * 1e6, "window_t1_us": t1 * 1e6,
            "spans": [e for e in obs.tracer.chrome_events()
                      if e["ph"] in ("B", "E")],
            "registry_t0": registry_t0, "registry_t1": registry_t1}


def counter_delta(run: dict, name: str) -> float:
    """How much the program's metric ``name`` moved over the window, from
    the registry snapshots a driver takes as it opens and closes."""
    return run["registry_t1"].get(name, 0) - run["registry_t0"].get(name, 0)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of all samples (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Check:
    """One number compared with its limit: ok when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value) and self.value <= self.limit)


@dataclasses.dataclass
class Result:
    """What a driver hands back to bench/run.py. ``layer_data`` is what
    every per-layer reader sees (with the trace's summary added): the
    window's length and bounds on the host clock, the chips, the device's
    kind, the cell's configuration, the program's host spans, and its
    whole metrics registry as the window opened and as it closed."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Check]
    device: Dict[str, Any]
    layer_data: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace_dir: Optional[Path] = None
    compiles_in_window: int = 0
