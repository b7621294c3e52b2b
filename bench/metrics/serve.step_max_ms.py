"""serve.step_max_ms: the longest engine step of the window: the longest
``serve.step`` span (launch/serve.py ``ServeEngine.step``) of the
program's tracer that starts in the window. A note splits it among the
spans open in it (its phases, and ``host.gc`` for a GC pass), each
instant going to the innermost."""
from bench.lib import harness as H
from bench.lib.layers import innermost

STEP = "serve.step"


def _spans(events):
    """(start, end, name, tid) of each closed span, in microseconds."""
    open_, out = {}, []
    for e in sorted(events, key=lambda e: e["ts"]):
        stack = open_.setdefault(e["tid"], [])
        if e["ph"] == "B":
            stack.append(e)
        elif stack and stack[-1]["name"] == e["name"]:
            b = stack.pop()
            out.append((b["ts"], e["ts"], e["name"], e["tid"]))
    return out


def read(run: dict):
    spans = _spans(run["spans"])
    steps = [s for s in spans if s[2] == STEP and
             run["window_t0_us"] <= s[0] < run["window_t1_us"]]
    if not steps:
        return None
    a, b, _, tid = max(steps, key=lambda s: s[1] - s[0])
    inside = [(max(sa, a), min(sb, b), n) for sa, sb, n, t in spans
              if sb > a and sa < b and (t == tid or n == "host.gc")]
    split = {}
    for pa, pb, chain in innermost(inside, a, b):
        split[chain[-1]] = split.get(chain[-1], 0.0) + (pb - pa) / 1e3
    H.note(f"longest {STEP} {(b - a) / 1e3:.3f} ms at "
           f"{(a - run['window_t0_us']) / 1e6:.3f} s into the window: " +
           " ".join(f"{n}={t:.3f}" for n, t in
                    sorted(split.items(), key=lambda kv: -kv[1])))
    return (b - a) / 1e3
