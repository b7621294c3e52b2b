"""train.scan_ms: device time of the selective scan per train step: the
self time of the device ops under the program's ``mamba.scan`` scope
(models/blocks.py ``apply_mamba``), forward and backward, in the traced
window, over the window's steps (bench/lib/layers.py)."""
from bench.lib.layers import per_step_ms


def read(run: dict):
    return per_step_ms(run, ("mamba.scan",))
