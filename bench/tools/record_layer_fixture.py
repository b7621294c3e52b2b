"""Record the small device trace that bench/tests/test_bench_layers.py
reads, with the program's own spans and scopes in it. On the chip:

    python bench/tools/record_layer_fixture.py <out_dir>

Inside a ``bench.window`` annotation, with the program's recording tracer
(``repro.obs``), it runs three rounds of:

* a ``train.step`` span over 20 calls of a jitted program with three
  named scopes, ``mamba.in_proj`` (one matmul), ``mamba.scan`` (six) and
  ``mamba.conv`` (two), so the scan scope holds about three times the
  conv scope's device time, and the conv scope twice the projection's;
* a ``bench.engine_step`` annotation holding a ``serve.step`` span, which
  holds a ``serve.decode_emit`` span with a 20 ms pause and pauses 10 ms
  itself; the annotation alone pauses 5 ms more;
* a ``train.host`` span with one full GC pass (a ``host.gc`` span) over
  cyclic garbage, then a 5 ms pause.

The device is idle in the pauses: per round about 20 ms go to
``serve.decode_emit``, 10 ms to ``serve.step``, 5 ms to
``bench.engine_step``, and to ``train.host`` 5 ms besides the time it
takes to make the garbage, whose collection goes to ``host.gc``. Beside
the trace it writes ``spans.json``: the tracer's begin/end events and the
window's bounds on the tracer's clock, as a driver hands them to the
readers.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    import jax
    import jax.numpy as jnp
    from bench.lib import xtrace
    from repro.obs import Obs

    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)

    @jax.jit
    def f(x):
        with jax.named_scope("mamba.in_proj"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("mamba.scan"):
            for _ in range(6):
                y = jnp.tanh(y @ x)
        with jax.named_scope("mamba.conv"):
            for _ in range(2):
                y = jnp.tanh(y @ x)
        return y

    x = jnp.full((2048, 2048), 1e-3, jnp.bfloat16)
    jax.block_until_ready(f(x))
    obs = Obs.on()
    tr = obs.tracer
    xtrace.start(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        for _ in range(3):
            with tr.span("train.step", track="train"):
                y = x
                for _ in range(20):
                    y = f(y)
                jax.block_until_ready(y)
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                with tr.span("serve.step", track="engine"):
                    with tr.span("serve.decode_emit", track="engine"):
                        time.sleep(0.02)
                    time.sleep(0.01)
                time.sleep(0.005)
            with tr.span("train.host", track="train"):
                gc.disable()             # one pass, not one per 700 lists
                junk = [[] for _ in range(200_000)]
                for a, b in zip(junk, junk[1:]):
                    a.append(b)
                    b.append(a)
                del junk
                gc.enable()
                gc.collect()
                time.sleep(0.005)
        t1 = time.perf_counter()
    jax.profiler.stop_trace()
    obs.close()
    spans = [e for e in tr.chrome_events() if e["ph"] in ("B", "E")]
    (out / "spans.json").write_text(json.dumps(
        {"window_t0_us": t0 * 1e6, "window_t1_us": t1 * 1e6,
         "spans": spans}))
    print("recorded", out)


if __name__ == "__main__":
    main()
