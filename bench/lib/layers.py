"""A traced window read by the program's own names: the spans that the
program's tracer (``repro.obs``) enters as profiler annotations, and the
named scopes (``jax.named_scope``) in the metadata of the device's ops.

bench/lib/xtrace.py reduces the trace to busy time, idle gaps split among
the benchmark's ``bench.*`` annotations, top ops and modules; this module
reads the same trace again for what that reduction does not keep:

* idle by span: each idle gap of the device inside ``bench.window`` is cut
  at the edges of the host annotations open in it, and each piece goes to
  the innermost annotation open over it (the one that started last), the
  program's spans and ``bench.*`` alike; a piece under none goes to
  ``host.unannotated``;
* device time by scope: the self time of each op (xtrace.self_times) goes
  to the innermost program scope named in its ``tf_op`` metadata (the
  op_name XLA keeps from the traced program, e.g.
  ``jit(step_fn)/transpose(jvp())/while/body/mamba.scan/mul``); an op
  under none goes to ``unscoped``.

The readers receive the trace's summary, not its path: ``for_run`` finds
the newest ``.xplane.pb`` under the benchmark's output directory whose
``bench.window`` lasts exactly as long as the summary's window, and reads
nothing when there is none. A program without these spans or scopes (one
older than them) leaves the tables without them, and the readers return
None.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench.lib import harness as H
from bench.lib import xtrace

UNSCOPED = "unscoped"
# the program's scopes: models/blocks.py, models/lm.py, optim/adamw.py
SCOPE = re.compile(r"(?<![\w.])(?:mamba|lm|optim)\.[a-z_]+")


def program_prefixes() -> Tuple[str, ...]:
    """Name prefixes of the program's profiler annotations (none where the
    program does not export them)."""
    try:
        from repro.obs import SPAN_PREFIXES
    except ImportError:
        return ()
    return tuple(SPAN_PREFIXES)


# ------------------------------------------------ op metadata (tf_op)
def _varint(b, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        if x < 0x80:
            return r, i
        s += 7


def _fields(b, i: int, end: int):
    """(field, value) of a protobuf message in ``b[i:end]``; a
    length-delimited value is its (start, end), not decoded."""
    while i < end:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = None, i + 8
        elif wt == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wt == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wt} at {i}")
        yield f, v


def _str(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def op_metadata(path) -> Dict[str, str]:
    """HLO op text (the name of its ``XLA Ops`` events) -> its ``tf_op``
    metadata, for the device planes of an ``XSpace`` file (tsl's
    xplane.proto: XSpace.planes 1; XPlane.name 2, .event_metadata 4,
    .stat_metadata 5; XEventMetadata.name 2, .stats 5; XStat.metadata_id
    1, .str_value 5, .ref_value 7). Only the metadata is decoded; the
    planes' event lines are skipped."""
    b = memoryview(Path(path).read_bytes())
    out: Dict[str, str] = {}
    for f, plane in _fields(b, 0, len(b)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(b, *plane):
            if pf == 2:
                name = _str(b, pv)
            elif pf == 4:
                events.append(pv)
            elif pf == 5:
                for ef, ev in _fields(b, *pv):
                    if ef == 2:
                        d = dict(_fields(b, *ev))
                        stat_names[d.get(1, 0)] = _str(b, d[2]) \
                            if 2 in d else ""
        if not name.startswith("/device:"):
            continue
        for entry in events:
            for ef, ev in _fields(b, *entry):
                if ef != 2:
                    continue
                op, tf_op = "", None
                for mf, mv in _fields(b, *ev):
                    if mf == 2:
                        op = _str(b, mv)
                    elif mf == 5:
                        st = dict(_fields(b, *mv))
                        if stat_names.get(st.get(1)) != "tf_op":
                            continue
                        if 5 in st:
                            tf_op = _str(b, st[5])
                        elif 7 in st:
                            tf_op = stat_names.get(st[7], "")
                if op and tf_op is not None:
                    out.setdefault(op, tf_op)
    return out


def scope_of(tf_op: Optional[str]) -> str:
    """The innermost program scope named in an op's metadata."""
    found = SCOPE.findall(tf_op or "")
    return found[-1] if found else UNSCOPED


# ------------------------------------------------------- the reading
@dataclasses.dataclass
class Layers:
    window_s: float
    # seconds of device idle by innermost host annotation, per device
    idle_by_span: Dict[str, float]
    # seconds of device idle under each annotation (at any depth)
    idle_under: Dict[str, float]
    # seconds of device self time by program scope, per device
    scope_s: Dict[str, float]
    # (scope/op, seconds) by self time, per device
    top_ops: List[Tuple[str, float]]
    # program annotations that start in the window, by name
    span_counts: Dict[str, int]
    # the longest idle gaps: (seconds, [(innermost span, seconds)] in time
    # order; an unannotated piece names the spans that end before it and
    # start after it)
    longest_gaps: List[Tuple[float, List[Tuple[str, float]]]]


def _outer_first(span):
    return span[0], -span[1]


def innermost(spans, lo, hi):
    """Pieces (a, b, chain) of [lo, hi) cut at the edges of ``spans``
    ((start, end, name)); ``chain`` names the spans open over the piece,
    outermost first: the last one started last."""
    edges = sorted({lo, hi} | {x for a, b, _ in spans for x in (a, b)
                               if lo < x < hi})
    starts = sorted(spans, key=_outer_first)
    out, active, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][0] <= a:
            active.append(starts[k])
            k += 1
        active = [s for s in active if s[1] > a]
        out.append((a, b, tuple(n for _, _, n in
                                sorted(active, key=_outer_first))))
    return out


def reduce(path) -> Optional[Layers]:
    """The reading of one ``.xplane.pb`` file; None without a
    ``bench.window`` or a TPU plane with device ops."""
    prof = xtrace.load(path)
    prefixes = program_prefixes()
    spans = []                           # (start, end, name) host
    for pl in prof.planes:
        if not pl.name.startswith("/host:"):
            continue
        for line in pl.lines:
            for name, a, b in xtrace._events(line):
                if name.startswith("bench.") or (
                        prefixes and name.startswith(prefixes)):
                    spans.append((a, b, name))
    wins = [(a, b) for a, b, n in spans if n == xtrace.WINDOW]
    if not wins:
        return None
    lo, hi = wins[0]
    inner = sorted((s for s in spans if s[2] != xtrace.WINDOW
                    and s[1] > lo and s[0] < hi), key=_outer_first)
    meta = op_metadata(path)
    idle_by = collections.Counter()
    under = collections.Counter()
    scope = collections.Counter()
    ops = collections.Counter()
    gaps = []
    n_dev = 0
    for pl in prof.planes:
        if not pl.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in pl.lines}
        if xtrace.OPS_LINE not in lines:
            continue
        n_dev += 1
        evs = [(n, max(a, lo), min(b, hi)) for n, a, b in
               xtrace._events(lines[xtrace.OPS_LINE]) if b > lo and a < hi]
        for name, t in xtrace.self_times(evs).items():
            sc = scope_of(meta.get(name))
            scope[sc] += t
            ops[f"{sc}/{xtrace.short_name(name)}"] += t
        busy = xtrace.union([(a, b) for _, a, b in evs])
        idle = [(a, b) for a, b in zip([lo] + [e for _, e in busy],
                                       [s for s, _ in busy] + [hi])
                if b > a]
        open_, k = [], 0                 # spans that may overlap the gap
        for ga, gb in idle:
            while k < len(inner) and inner[k][0] < gb:
                open_.append(inner[k])
                k += 1
            open_ = [s for s in open_ if s[1] > ga]
            pieces = innermost(open_, ga, gb)
            for a, b, chain in pieces:
                t = (b - a) / 1e9
                idle_by[chain[-1] if chain else xtrace.UNANNOTATED] += t
                for n in set(chain):
                    under[n] += t
            gaps.append(((gb - ga) / 1e9, pieces))
    if not n_dev:
        return None
    counts = collections.Counter(n for a, _, n in inner if lo <= a < hi)
    ends = sorted((b, n) for _, b, n in inner)
    starts = sorted((a, n) for a, _, n in inner)

    def label(a, b, chain):
        if chain:
            return chain[-1]
        i = bisect.bisect_right(ends, (a, "\uffff"))
        j = bisect.bisect_left(starts, (b, ""))
        return (f"{xtrace.UNANNOTATED}("
                f"{ends[i - 1][1] if i else ''}|"
                f"{starts[j][1] if j < len(starts) else ''})")

    gaps.sort(key=lambda g: -g[0])
    longest = [(t, [(label(a, b, c), (b - a) / 1e9) for a, b, c in p])
               for t, p in gaps[:10]]
    per = 1.0 / n_dev
    return Layers(window_s=(hi - lo) / 1e9,
                  idle_by_span={k: v * per for k, v in idle_by.items()},
                  idle_under={k: v * per for k, v in under.items()},
                  scope_s={k: v * per for k, v in scope.items()},
                  top_ops=[(k, v * per) for k, v in ops.most_common()],
                  span_counts=dict(counts), longest_gaps=longest)


_CACHE: Dict[str, Optional[Layers]] = {}
_NOTED: set = set()


def for_run(run: dict) -> Optional[Layers]:
    """The reading of this run's trace, or None when the run has no trace
    summary or its trace file is not found. Read once per file."""
    summary = run.get("trace")
    if summary is None:
        return None
    found = sorted(glob.glob(str(H.OUT / "**" / "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        return None
    path = found[-1]
    if path not in _CACHE:
        _CACHE[path] = reduce(path)
    got = _CACHE[path]
    if got is None or got.window_s != summary.window_s:
        return None
    if path not in _NOTED:
        _NOTED.add(path)
        note(got)
    return got


def note(r: Layers) -> None:
    """The tables behind the readings, for the record."""
    dev = sum(r.scope_s.values())
    if dev:
        H.note("device self time by scope (s): " + " ".join(
            f"{k}={v:.4f}" for k, v in sorted(r.scope_s.items())) +
            f"; unscoped {100 * r.scope_s.get(UNSCOPED, 0) / dev:.2f}%")
        H.note("top ops by scope: " + "; ".join(
            f"{n} {t:.4f} s" for n, t in r.top_ops[:10]))
    idle = sum(r.idle_by_span.values())
    if idle:
        named = idle - r.idle_by_span.get(xtrace.UNANNOTATED, 0.0)
        H.note("idle by innermost span (s): " + " ".join(
            f"{k}={v:.4f}" for k, v in sorted(r.idle_by_span.items())) +
            f"; named {100 * named / idle:.2f}% of {idle:.4f} s")
        H.note("longest idle gaps (ms): " + "; ".join(
            f"{1e3 * t:.3f} = " + " + ".join(
                f"{n} {1e3 * p:.3f}" for n, p in pieces)
            for t, pieces in r.longest_gaps[:5]))


def per_step_ms(run: dict, scopes) -> Optional[float]:
    """Device self time (ms) of the ops under ``scopes``, per train step of
    the window (the Trainer's ``train.steps`` counter)."""
    r = for_run(run)
    steps = H.counter_delta(run, "train.steps")
    if r is None or not steps:
        return None
    found = [r.scope_s[s] for s in scopes if s in r.scope_s]
    return 1e3 * sum(found) / steps if found else None
