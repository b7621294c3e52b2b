"""train.data_wait_ms: mean time per step that the Trainer waited for its
loader (data/packing_loader.py behind data/prefetch.py), from the
Trainer's own ``train.data`` spans that start in the window."""
from bench.lib.spans import durations_ms


def read(run: dict):
    d = durations_ms(run["spans"], "train.data", run["window_t0_us"],
                     run["window_t1_us"])
    return sum(d) / len(d) if d else None
