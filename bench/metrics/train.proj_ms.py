"""train.proj_ms: device time of the projections per train step: the self
time of the device ops under the program's ``mamba.in_proj``,
``mamba.x_proj`` (with the dt projection), ``mamba.out_proj`` (with the
gate and the residual) and ``lm.head`` (final norm, head and loss)
scopes, forward and backward, in the traced window, over the window's
steps (bench/lib/layers.py)."""
from bench.lib.layers import per_step_ms


def read(run: dict):
    return per_step_ms(run, ("mamba.in_proj", "mamba.x_proj",
                             "mamba.out_proj", "lm.head"))
