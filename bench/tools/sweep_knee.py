"""Find the knee of a serve cell once, on the chip: the highest arrival
rate whose backlog does not grow over a window.

    python bench/tools/sweep_knee.py --workload serve.mamba-110m.chat \
        --seconds 20 2 4 6 8 12

One engine is built and warmed as the cell's driver does; each rate then
gets an open-loop window of the cell's traffic. Printed per rate: requests
due, finished in the window, the backlog (submitted, not finished) at a
third, two thirds and the end of the window, with how many of them
are still queued for a slot, and the p95 of time to first token of those
finished. The cell's ``rate_rps`` is then set by hand to about
0.8 of the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def window(eng, reqs, seconds: float) -> dict:
    import numpy as np
    t0 = time.perf_counter()
    live, first, done, backlog = {}, {}, {}, {}
    i = 0
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        while i < len(reqs) and reqs[i].due_s <= now:
            live[eng.submit(reqs[i].prompt, reqs[i].max_new)] = i
            i += 1
        for k, f in (("third", 1 / 3), ("two_thirds", 2 / 3)):
            if k not in backlog and now >= seconds * f:
                backlog[k] = len(live)
                backlog["queued_" + k] = len(eng.queue)
        if live:
            eng.step()
            seen = time.perf_counter() - t0
            for rid, k in list(live.items()):
                if k not in first and eng.outputs[rid]:
                    first[k] = seen
                if eng.status[rid] not in ("queued", "active"):
                    done[k] = seen
                    del live[rid]
        else:
            time.sleep(0.001)
    backlog["end"] = len(live)
    backlog["queued_end"] = len(eng.queue)
    eng.run()                                  # drain before the next rate
    ttft = [(first[k] - reqs[k].due_s) * 1e3 for k in done]
    return {"due": i, "finished": len(done), **backlog,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) if ttft else None}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("rates", nargs="+", type=float)
    args = ap.parse_args()
    from bench.lib import harness as H
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(H.COMPILE_CACHE)
    from bench.drivers import serve as D
    from bench.lib.model import jax_key, sizes
    from bench.lib.traffic import chat_requests
    H.require_devices(1)
    cell = H.find_cell(args.workload)
    t, conf = cell.traffic, cell.config
    s = sizes(conf)
    params = D.make_params(conf)(jax_key(args.seed, 1))
    eng, _ = D.build_engine(conf, t, params)
    D._warm_up(eng, s.vocab_real)
    for rate in args.rates:
        reqs = chat_requests(t, args.seed, s.vocab_real, rate, args.seconds)
        print(json.dumps({"rate_rps": rate, **window(eng, reqs,
                                                     args.seconds)}),
              flush=True)


if __name__ == "__main__":
    main()
