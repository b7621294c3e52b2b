"""train.mfu: the whole packed step's share of the chip's bf16 peak. The
work is what training requires for the window's real tokens, counted by
the Trainer's ``train.real_tokens`` counter, at bench/lib/flops.py's
count per token (forward and backward, recomputation not counted); the
time is the window's, on the host clock; the peak is bench/peaks.json's
for the device's kind."""
from bench.lib.flops import train_flops_per_token
from bench.lib.harness import counter_delta, peaks
from bench.lib.model import sizes


def read(run: dict):
    real = counter_delta(run, "train.real_tokens")
    if not real:
        return None
    flops = real * train_flops_per_token(sizes(run["config"]))
    peak = peaks(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (run["window_s"] * run["chips"] * peak)
