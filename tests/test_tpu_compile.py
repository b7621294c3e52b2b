"""Every Pallas kernel build compiles for a TPU v5e at real widths.

The TPU compiler is installed even where no chip is attached: each case
lowers one kernel entry point of kernels/ops.py (forward, or forward plus
the custom_vjp backward) for one device of a described ``v5e:2x2``
topology and compiles it, so tiling, layout and VMEM refusals surface here
and not on the chip. Mamba-1 kernels run at ``mamba-110m`` widths
(d_inner 2048, N 16), the head-structured kernels at ``mamba2-370m`` widths
(32 heads of 64, N 64); sequence length 4096 as in packed training.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. The persistent compilation cache is off around these compiles
(an entry written for a chip that is not attached cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, L = 2, 4096
DI, N = 2048, 16                # mamba-110m: d_inner, d_state
H, P, N2 = 32, 64, 64           # mamba2-370m: heads, head dim, d_state
BF = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _grad(f):
    """f(*diff, positions) → its VJP w.r.t. every operand but positions."""
    def g(*args):
        *diff, pos = args
        loss = lambda *d: (f(*d, pos).astype(jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=tuple(range(len(diff))))(*diff)
    return g


def _conv(x, w, b, pos):
    return ops.conv1d_pack(x, w, b, pos, backend="pallas")


def _scan(schedule):
    def f(u, dt, A, Bm, Cm, D, pos):
        return ops.selective_scan(u, dt, A, Bm, Cm, D, pos, backend="pallas",
                                  schedule=schedule)
    return f


def _heads(schedule):
    def f(u, dt, A, Bm, Cm, D, pos):
        return ops.selective_scan_heads(u, dt, A, Bm, Cm, D, pos,
                                        backend="pallas", schedule=schedule)
    return f


def _operands(kind):
    """(dtype, shape) of each operand, positions last."""
    pos = ((B, L), jnp.int32)
    if kind == "conv":
        return [((B, L, DI), BF), ((4, DI), BF), ((DI,), BF), pos]
    if kind == "scan":
        return [((B, L, DI), BF), ((B, L, DI), BF), ((DI, N), jnp.float32),
                ((B, L, N), BF), ((B, L, N), BF), ((DI,), jnp.float32), pos]
    return [((B, L, H, P), BF), ((B, L, H), BF), ((H,), jnp.float32),
            ((B, L, N2), BF), ((B, L, N2), BF), ((H,), jnp.float32), pos]


CASES = {
    "conv1d_pack": ("conv", _conv),
    "scan_blocked": ("scan", _scan("blocked")),
    "scan_step": ("scan", _scan("step")),
    "heads_blocked_heads": ("heads", _heads("blocked_heads")),
    "heads_blocked_heads_dual": ("heads", _heads("blocked_heads_dual")),
}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name, direction):
    kind, f = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in _operands(kind)]
    fn = f if direction == "fwd" else _grad(f)
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    # the Mosaic kernel itself, not the interpreter's XLA loop
    assert "tpu_custom_call" in hlo
    assert hlo.count("tpu_custom_call") >= (1 if direction == "fwd" else 2)
