"""Record the small device trace that bench/tests/test_bench_xtrace.py
reads. On the chip:

    python bench/tools/record_trace_fixture.py <out_dir>

Inside a ``bench.window`` annotation it runs three rounds of: a jitted
matmul chain under ``bench.train_step``, then a host pause of 30 ms under
``bench.data`` and a pause of 20 ms under no annotation. The device is
idle in the pauses, so the reduction must find about 50 ms of idle time
per round, of which 30 ms are attributed to ``bench.data``.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    import jax
    import jax.numpy as jnp
    from bench.lib import xtrace
    out = sys.argv[1]
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(f(x))
    xtrace.start(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                y = x
                for _ in range(20):
                    y = f(y)
                jax.block_until_ready(y)
            with jax.profiler.TraceAnnotation("bench.data"):
                time.sleep(0.03)
            time.sleep(0.02)
    jax.profiler.stop_trace()
    print("recorded", out)


if __name__ == "__main__":
    main()
