"""Host spans of the program's own tracer (repro.obs, Chrome trace events
on the perf_counter clock, in microseconds)."""
from __future__ import annotations

from typing import List


def durations_ms(events: List[dict], name: str, t0_us: float,
                 t1_us: float) -> List[float]:
    """Durations of the spans called ``name`` that begin in [t0, t1)."""
    open_: dict = {}
    out = []
    for e in sorted(events, key=lambda e: e["ts"]):
        if e["name"] != name:
            continue
        key = e["tid"]
        if e["ph"] == "B":
            open_[key] = e["ts"]
        elif e["ph"] == "E" and key in open_:
            b = open_.pop(key)
            if t0_us <= b < t1_us:
                out.append((e["ts"] - b) / 1e3)
    return out
