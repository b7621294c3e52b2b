"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the device's busy
time, its idle gaps, and the time of named XLA modules.

The window is the host annotation ``bench.window`` that the drivers place
around the measured window. On each TPU plane the ``XLA Ops`` line holds
one event per operation run on the device; busy time is the union of
their intervals inside the window. Each idle gap is split among the
``bench.*`` host annotations (sibling spans that the benchmark places
around what its host does) by how much of the gap each covers, the rest
going to ``host.unannotated``; the gap itself is named after the largest
share. Module events (``XLA Modules``) give the device time of each
jitted program.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNANNOTATED = "host.unannotated"


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over the devices traced
    devices: int
    idle_by_host: Dict[str, float]      # seconds of idle, by host span
    longest_gaps: List[Tuple[str, float]]
    top_ops: List[Tuple[str, float]]
    modules: Dict[str, List[float]]     # module name -> durations (s)

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.longest_gaps[:10]]}


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


def short_name(hlo: str) -> str:
    """'%fusion.4 = f32[8,2048]{...} fusion(...)' -> 'fusion.4 f32[8,2048]'."""
    left, _, right = hlo.partition(" = ")
    return f"{left.lstrip('%')} {right.split('{')[0].split(' ')[0]}".strip()


def self_times(events) -> Dict[str, float]:
    """Seconds of each op's own time: an op that encloses others on the
    same line (a while loop and its body) keeps only the time its
    children do not cover."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[list] = []                 # [name, start, end, covered]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            n, sa, sb, cov = stack.pop()
            out[n] += (sb - sa - cov) / 1e9
        if stack:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0])
    while stack:
        n, sa, sb, cov = stack.pop()
        out[n] += (sb - sa - cov) / 1e9
    return out


def start(path) -> None:
    """Start the profiler without Python function tracing, which slows a
    host-bound loop such as the serving engine's several-fold."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)


def load(path) -> "object":
    from jax.profiler import ProfileData
    p = Path(path)
    if p.is_dir():
        found = sorted(glob.glob(str(p / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {p}")
        p = Path(found[-1])
    return ProfileData.from_file(str(p))


def summarize(path) -> Summary:
    prof = load(path)
    host = [pl for pl in prof.planes if pl.name.startswith("/host:")]
    devs = [pl for pl in prof.planes if pl.name.startswith("/device:TPU:")]
    spans = []                           # (start, end, name) of bench.*
    for pl in host:
        for line in pl.lines:
            for name, a, b in _events(line):
                if name.startswith("bench."):
                    spans.append((a, b, name))
    wins = [(a, b) for a, b, n in spans if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} annotation in the trace {path}")
    lo, hi = wins[0]
    inner = [(a, b, n) for a, b, n in spans if n != WINDOW]
    busy_total = 0
    idle_by = collections.Counter()
    gaps: List[Tuple[str, float]] = []
    ops = collections.Counter()
    modules: Dict[str, List[float]] = collections.defaultdict(list)
    n_dev = 0
    for pl in devs:
        lines = {ln.name: ln for ln in pl.lines}
        if OPS_LINE not in lines:
            continue
        n_dev += 1
        evs = [(short_name(n), max(a, lo), min(b, hi))
               for n, a, b in _events(lines[OPS_LINE]) if b > lo and a < hi]
        ops.update(self_times(evs))
        busy = union([(a, b) for _, a, b in evs])
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            share = collections.Counter()
            for sa, sb, n in inner:
                if sb > a and sa < b:
                    share[n] += min(sb, b) - max(sa, a)
            share[UNANNOTATED] = (b - a) - sum(share.values())
            for n, t in share.items():
                idle_by[n] += t / 1e9
            gaps.append((share.most_common(1)[0][0], (b - a) / 1e9))
        if MODULES_LINE in lines:
            for name, a, b in _events(lines[MODULES_LINE]):
                if lo <= a < hi:
                    modules[name].append((b - a) / 1e9)
    if not n_dev:
        raise ValueError(f"no TPU plane with an '{OPS_LINE}' line in {path}")
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_total / n_dev / 1e9,
                   devices=n_dev, idle_by_host=dict(idle_by),
                   longest_gaps=gaps, top_ops=ops.most_common(),
                   modules=dict(modules))


def idle_percent(s: Optional[Summary]) -> Optional[float]:
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)


def module_mean_ms(s: Optional[Summary], parts: Sequence[str]
                   ) -> Optional[float]:
    """Mean device time (ms) of module events whose name holds any of
    ``parts``; None when there are none."""
    if s is None:
        return None
    d = [x for name, xs in s.modules.items()
         if any(p in name for p in parts) for x in xs]
    return 1e3 * sum(d) / len(d) if d else None
