"""train.pack_fill: real tokens over buffer tokens of the window's steps,
from the Trainer's ``train.real_tokens`` and ``train.buffer_tokens``
counters."""
from bench.lib.harness import counter_delta


def read(run: dict):
    buf = counter_delta(run, "train.buffer_tokens")
    return counter_delta(run, "train.real_tokens") / buf if buf else None
