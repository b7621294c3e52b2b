"""train.collective_ms: device time per train step spent moving data
between chips: the self time of the all-gathers, reduce-scatters,
all-reduces, collective-permutes and all-to-alls that XLA put into the
sharded step, their async start and done halves and the fusions that
carry them, in the traced window, averaged over the traced chips (as
bench/lib/layers.py averages scopes) and divided by the window's steps
(the Trainer's ``train.steps`` counter). None on a trace with no such op.

An op is told by its HLO text, which is the name of its ``XLA Ops``
event: its instruction name (``%all-gather.70``,
``%collective-permute-start.1``, ``%async-collective-done``) or the
computation it calls (``%fusion.304 = ... calls=%all-reduce-scatter.1``)
begins with a collective's opcode. Operands are not looked at: a fusion
that only reads an all-gather's result is compute.
"""
import glob
import os
import re

from bench.lib import harness as H
from bench.lib import xtrace

OPCODES = re.compile(r"(all-gather|reduce-scatter|all-reduce|"
                     r"collective-permute|all-to-all|async-collective)")
CALLS = re.compile(r"calls=%([\w.-]+)")


def is_collective(op: str) -> bool:
    name = op.partition(" = ")[0].strip().lstrip("%")
    calls = CALLS.search(op)
    return bool(OPCODES.match(name) or
                (calls and OPCODES.match(calls.group(1))))


def collective_s(path, window_s: float):
    """Seconds of collective self time per traced chip in the
    ``bench.window`` of the trace ``path``; None when its window is not
    ``window_s`` long or no chip ran a collective."""
    prof = xtrace.load(path)
    wins = [(a, b) for pl in prof.planes if pl.name.startswith("/host:")
            for line in pl.lines for n, a, b in xtrace._events(line)
            if n == xtrace.WINDOW]
    if not wins or (wins[0][1] - wins[0][0]) / 1e9 != window_s:
        return None
    lo, hi = wins[0]
    total, n_dev = 0.0, 0
    for pl in prof.planes:
        if not pl.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in pl.lines}
        if xtrace.OPS_LINE not in lines:
            continue
        n_dev += 1
        evs = [(n, max(a, lo), min(b, hi)) for n, a, b in
               xtrace._events(lines[xtrace.OPS_LINE]) if b > lo and a < hi]
        total += sum(t for n, t in xtrace.self_times(evs).items()
                     if is_collective(n))
    return total / n_dev if total else None


def read(run: dict):
    summary = run.get("trace")
    steps = H.counter_delta(run, "train.steps")
    if summary is None or not steps:
        return None
    found = sorted(glob.glob(str(H.OUT / "**" / "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        return None
    s = collective_s(found[-1], summary.window_s)
    return None if s is None else 1e3 * s / steps
