"""Pallas TPU kernels for the paper's packed hot path (conv1d_pack and the
segmented selective scan), their jit'd wrappers (ops.py) and pure-jnp
oracles (ref.py)."""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` that picks interpret mode from the platform the
    call is lowered for: compiled by Mosaic on a TPU, run under the Pallas
    interpreter everywhere else (the CPU test path). The choice is made at
    lowering time, so a TPU never runs the interpreter and a compile for a
    described TPU that is not attached still compiles the real kernel."""
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)

    return call
