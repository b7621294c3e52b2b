"""Every Pallas kernel build compiles for a TPU v5e at real widths, and
the packed train step runs the scan kernel there and only there.

The TPU compiler is installed even where no chip is attached: each case
lowers one kernel entry point of kernels/ops.py (forward, or forward plus
the custom_vjp backward) for one device of a described ``v5e:2x2``
topology and compiles it, so tiling, layout and VMEM refusals surface here
and not on the chip. Mamba-1 kernels run at ``mamba-110m`` widths
(d_inner 2048, N 16), the head-structured kernels at ``mamba2-370m`` widths
(32 heads of 64, N 64); sequence length 4096 as in packed training.

The train step cases build the Trainer's step for ``mamba-110m`` at its
widths (two layers, a small vocabulary) with the program's defaults. Its
scan picks the kernel from the default backend while tracing, which here
is the CPU: the TPU cases trace as a TPU process would (``on_tpu``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. The persistent compilation cache is off around these compiles
(an entry written for a chip that is not attached cannot be read back).
"""
import dataclasses
import os
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.base import get_config
from repro.distributed import sharding as shd
from repro.kernels import ops
from repro.models.lm import build_model
from repro.optim.adamw import AdamW, constant_schedule
from repro.train.trainer import Trainer, TrainerConfig

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


def _scan(u, dt, A, Bm, Cm, D, pos):
    return ops.selective_scan(u, dt, A, Bm, Cm, D, pos, backend="pallas")


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
    "scan_step": ("scan", _scan),
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


_CELL_SHAPES = [
    (8, 4096, 2048),        # train.mamba-110m.pack4k
    (4, 4096, 4096),        # train.mamba-1.4b-s12.pack4k
    (4, 4096, 2048),        # train.mamba-1.4b.pack4k.x4, per shard
]


@pytest.mark.parametrize("direction,rows,length,channels", [
    pytest.param(d, *shape, id=("fwd-" if d == "fwd" else "") +
                 "-".join(map(str, shape)))
    for d in ("bwd", "fwd") for shape in _CELL_SHAPES])
def test_scan_step_bwd_compiles_for_v5e_at_cell_shapes(one_chip, direction,
                                                       rows, length,
                                                       channels):
    """The scan kernel at the train cells' per-device kernel shapes, its
    forward alone and with the backward: the backward's state buffer and
    the row group's bulk adjoint tiles fit the scoped VMEM (Mosaic refuses
    the compile otherwise)."""
    shapes = [((rows, length, channels), BF), ((rows, length, channels), BF),
              ((channels, N), jnp.float32), ((rows, length, N), BF),
              ((rows, length, N), BF), ((channels,), jnp.float32),
              ((rows, length), jnp.int32)]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in shapes]
    fn = _scan if direction == "fwd" else _grad(_scan)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = _kernel_calls(hlo)
    want = {2} if direction == "fwd" else {2, 6}        # forward, backward
    assert {n for n, _ in calls} == want, calls


# ---------------------------------------------------------------------------
# the packed train step: the scan kernel on a TPU, the XLA scan on the CPU
# ---------------------------------------------------------------------------

ROWS, SEQ = 2, 512


@pytest.fixture
def on_tpu(monkeypatch):
    """Trace as a process whose default backend is a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _trainer(mesh=None, **options):
    cfg = dataclasses.replace(get_config("mamba-110m"), n_layers=2,
                              vocab=512, **options)
    return Trainer(build_model(cfg), AdamW(constant_schedule(1e-3)), None,
                   TrainerConfig(), mesh=mesh)


def _lower_step(trainer, state_sharding=None, batch_sharding=None):
    """The Trainer's jitted step lowered for its state and a packed batch,
    each leaf on the given sharding (a tree for the state)."""
    state = jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0))
    if state_sharding is not None:
        if not isinstance(state_sharding, dict):
            state_sharding = jax.tree.map(lambda _: state_sharding, state)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            state, state_sharding)
    batch = {k: jax.ShapeDtypeStruct(
        (ROWS, SEQ), jnp.int32,
        sharding=(batch_sharding[k] if isinstance(batch_sharding, dict)
                  else batch_sharding))
        for k in ("tokens", "positions", "segment_ids")}
    return trainer.step_fn.lower(state, batch)


def _kernel_calls(hlo: str):
    """(outputs, op_name) of each Mosaic call: the scan's forward returns
    (y, checkpoints), its backward six gradients."""
    calls = []
    for line in hlo.splitlines():
        m = re.search(r"= \((.*)\) custom-call\(.*"
                      r'custom_call_target="tpu_custom_call".*'
                      r'op_name="([^"]*)"', line)
        if m:
            calls.append((len(re.findall(r"[a-z0-9]+\[", m.group(1))),
                          m.group(2)))
    return calls


def _assert_scan_kernel(hlo: str):
    calls = _kernel_calls(hlo)
    assert all("mamba.scan" in name for _, name in calls), calls
    assert {n for n, _ in calls} == {2, 6}, calls      # forward, backward


def _instructions(hlo: str):
    """HLO instructions without metadata and instruction numbering."""
    out = []
    for line in hlo.splitlines():
        line = re.sub(r", metadata=\{[^}]*\}|,? ?stack_frame_id=\d+", "",
                      line)
        if "=" in line and not re.match(r"^\s*\d+ ", line):
            out.append(re.sub(r"([%\w-]+?)\.\d+\b", r"\1", line))
    return out


def test_train_step_runs_scan_kernel_on_v5e(one_chip, on_tpu):
    lowered = _lower_step(_trainer(), one_chip, one_chip)
    _assert_scan_kernel(lowered.compile().as_text())
    # no (B, 32, 32, d_inner, N) masked decay of the XLA blocked evaluator
    assert f"x32x32x{DI}x{N}x" not in lowered.as_text()


def test_train_step_on_cpu_keeps_xla_scan():
    hlo = _lower_step(_trainer()).compile().as_text()
    assert "tpu_custom_call" not in hlo
    xla = _lower_step(_trainer(use_pallas=False)).compile().as_text()
    assert _instructions(hlo) == _instructions(xla)


def test_sharded_train_step_compiles_for_v5e_2x2(topo, on_tpu):
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    trainer = _trainer(mesh)
    batch = {k: jax.ShapeDtypeStruct((ROWS, SEQ), jnp.int32)
             for k in ("tokens", "positions", "segment_ids")}
    lowered = _lower_step(trainer, trainer.state_sharding,
                          shd.named(mesh, shd.batch_pspecs(batch, mesh)))
    # the kernel runs per shard (shard_map), next to the step's collectives
    hlo = lowered.compile().as_text()
    _assert_scan_kernel(hlo)
    assert "all-reduce" in hlo
