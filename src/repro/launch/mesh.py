"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — smoke tests keep their 1 CPU device; only
launch/dryrun.py (which sets XLA_FLAGS first) materializes 512 devices.
Every axis is ``AxisType.Auto``: shardings come from the partition specs in
distributed/sharding.py and XLA propagates the rest.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever devices exist, as ("data", "model") — the multi-device
    training path of launch/train.py and chip_smoke.py --chips 4."""
    n = len(jax.devices())
    if n % model_axis:
        raise ValueError(f"{n} devices not divisible by model={model_axis}")
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))
