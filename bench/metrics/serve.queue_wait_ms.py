"""serve.queue_wait_ms: mean time a request spent queued in the engine
(launch/serve.py ``ServeEngine``), from its per-request ``queued`` spans
that start in the window."""
from bench.lib.spans import durations_ms


def read(run: dict):
    d = durations_ms(run["spans"], "queued", run["window_t0_us"],
                     run["window_t1_us"])
    return sum(d) / len(d) if d else None
