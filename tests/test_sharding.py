"""Sharding rules + mini distributed dry-runs.

Rules are tested in-process against fake meshes (no devices needed);
actual sharded lower/compile/run happens in a subprocess with
--xla_force_host_platform_device_count=8 so the main pytest process keeps
its single CPU device (per the dry-run isolation requirement).
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.distributed import sharding as shd
from repro.models.lm import build_model


class FakeMesh:
    """Duck-typed mesh: only .shape (dict) is used by the rules."""
    def __init__(self, **axes):
        self.shape = axes


def test_param_rules_divisibility_guard():
    mesh = FakeMesh(data=16, model=16)
    # divisible: sharded
    assert shd._param_rule("w_gate", (2048, 5632), mesh) == P("data", "model")
    # non-divisible dim: that axis dropped
    assert shd._param_rule("w_gate", (2048, 5630), mesh) == P("data", None)
    assert shd._param_rule("embed", (50280, 64), mesh) == P(None, "data")
    # 1-device mesh: everything falls back to replication
    one = FakeMesh(data=1, model=1)
    spec = shd._param_rule("w_gate", (8, 8), one)
    assert spec == P("data", "model")      # axis size 1 divides everything


def test_moe_expert_rules():
    mesh = FakeMesh(data=16, model=16)
    # 64 experts: EP over model
    assert shd._param_rule("experts_gate", (64, 2048, 1408), mesh) == \
        P("model", "data", None)
    # 8 experts < 16: TP inside expert
    assert shd._param_rule("experts_gate", (8, 6144, 16384), mesh) == \
        P(None, "data", "model")
    assert shd._param_rule("experts_down", (8, 16384, 6144), mesh) == \
        P(None, "model", "data")


def test_param_pspecs_tree_matches_params():
    mesh = FakeMesh(data=4, model=2)
    cfg = get_config("recurrentgemma-2b").reduced()
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_pspecs(shapes, mesh)
    flat_shapes = jax.tree_util.tree_flatten_with_path(shapes)[0]
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    for (path, leaf), spec in zip(flat_shapes, flat_specs):
        assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
        # every sharded dim must divide
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if ax is not None:
                size = {"data": 4, "model": 2}[ax if isinstance(ax, str)
                                               else ax[0]]
                assert dim % size == 0, (path, spec, leaf.shape)


def test_batch_axis_fallbacks():
    mesh = FakeMesh(pod=2, data=16, model=16)
    assert shd.batch_axis(mesh, 256) == ("pod", "data")
    assert shd.batch_axis(mesh, 16) == "data"
    assert shd.batch_axis(mesh, 1) is None
    single = FakeMesh(data=16, model=16)
    assert shd.batch_axis(single, 256) == "data"


MINI_DRYRUN = r"""
import jax, dataclasses
from repro.configs.base import get_config
from repro.launch.shapes import build_cell, SHAPES
from jax.sharding import AxisType
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
SHAPES["train_4k"] = dict(kind="train", seq=128, batch=8)
SHAPES["decode_32k"] = dict(kind="decode", seq=128, batch=8)
for arch in ARCHS:
    cfg = get_config(arch).reduced()
    for shape in ("train_4k", "decode_32k"):
        from repro.launch.shapes import cell_supported
        ok, _ = cell_supported(cfg, shape)
        if not ok:
            continue
        cell = build_cell(cfg, mesh, shape)
        with mesh:
            c = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                        out_shardings=cell.out_shardings).lower(
                *cell.args).compile()
        cost = c.cost_analysis()
        if isinstance(cost, list):   # pinned JAX: one dict per device
            cost = cost[0] if cost else {}
        assert cost.get("flops", 0) > 0
        print("OK", arch, shape)
print("ALL_OK")
"""


@pytest.mark.parametrize("archs", [["stablelm-1.6b", "mamba-110m"],
                                   ["mixtral-8x22b", "recurrentgemma-2b"]])
def test_sharded_compile_8dev(archs):
    src = f"ARCHS = {archs!r}\n" + MINI_DRYRUN
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "ALL_OK" in out.stdout, out.stderr[-2000:]


def test_sharded_train_step_numerics_8dev():
    """Sharded train step == single-device train step (same batch/params)."""
    src = r"""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs.base import get_config
from repro.models.lm import build_model
from repro.optim.adamw import AdamW, constant_schedule
from repro.train.trainer import make_train_step
from repro.distributed import sharding as shd
from jax.sharding import AxisType
from jax.sharding import NamedSharding, PartitionSpec as P

cfg = dataclasses.replace(get_config("mamba-110m").reduced(), dtype="float32")
model = build_model(cfg)
opt = AdamW(constant_schedule(1e-3))
step = make_train_step(model, opt)
rng = np.random.default_rng(0)
B, L = 8, 32
batch = {"tokens": jnp.asarray(rng.integers(1, cfg.vocab, (B, L)), jnp.int32),
         "positions": jnp.tile(jnp.arange(L)[None], (B, 1)),
         "segment_ids": jnp.ones((B, L), jnp.int32)}
params = model.init(jax.random.PRNGKey(0))
state = {"params": params, "opt": opt.init(params)}
ref_state, ref_metrics = jax.jit(step)(state, batch)

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
pspec = shd.param_pspecs(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                         mesh)
ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
state_spec = {"params": pspec, "opt": type(state["opt"])(
    step=P(), m=pspec, v=pspec)}
bspec = shd.batch_pspecs(batch, mesh)
with mesh:
    sh_state = jax.device_put(state, ns(state_spec))
    sh_batch = jax.device_put(batch, ns(bspec))
    out_state, metrics = jax.jit(step)(sh_state, sh_batch)
np.testing.assert_allclose(float(metrics["loss"]), float(ref_metrics["loss"]),
                           rtol=1e-5)
for a, b in zip(jax.tree.leaves(out_state["params"]),
                jax.tree.leaves(ref_state["params"])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5, rtol=1e-4)
print("NUMERIC_OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "NUMERIC_OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.parametrize("use_pallas", [None, True])
def test_trainer_mesh_places_state_and_batch_4dev(use_pallas):
    """Trainer(mesh=...) creates the state sharded by the param rules, keeps
    those shardings across steps, shards each batch over the data axis
    inside a ``train.place`` span (absent without a mesh), and trains to
    the same losses as the one-device Trainer — with the Pallas kernels
    too, whose scan then runs per shard (shard_map)."""
    src = f"USE_PALLAS = {use_pallas!r}\n" + r"""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from jax.sharding import PartitionSpec as P
from repro.configs.base import get_config
from repro.launch.mesh import make_local_mesh
from repro.models.lm import build_model
from repro.obs import Obs
from repro.optim.adamw import AdamW, constant_schedule
from repro.train.trainer import Trainer, TrainerConfig

cfg = dataclasses.replace(get_config("mamba-110m").reduced(), dtype="float32",
                          use_pallas=USE_PALLAS)
model = build_model(cfg)
rng = np.random.default_rng(0)
B, L = 4, 32
batches = [{"tokens": jnp.asarray(rng.integers(1, cfg.vocab, (B, L)),
                                  jnp.int32),
            "positions": jnp.tile(jnp.arange(L)[None] % 13, (B, 1)),
            "segment_ids": jnp.ones((B, L), jnp.int32)} for _ in range(3)]

class Loader:
    def batch(self, step):
        return batches[step]

def run(mesh):
    obs = Obs.on()
    tr = Trainer(model, AdamW(constant_schedule(1e-3)), Loader(),
                 TrainerConfig(steps=3, log_every=100), mesh=mesh, obs=obs)
    seen, hlo = [], []
    step_fn = tr.step_fn
    def spy(state, batch):
        seen.append(batch["tokens"].sharding)
        if not hlo:
            hlo.append(step_fn.lower(state, batch).as_text())
        return step_fn(state, batch)
    tr.step_fn = spy
    state, hist = tr.train(jax.random.PRNGKey(0), verbose=False)
    obs.close()
    places = [e for e in obs.tracer.chrome_events()
              if e["name"] == "train.place" and e["ph"] == "B"]
    return state, [h["loss"] for h in hist], seen, hlo[0], places

_, ref, _, _, places = run(None)
assert places == [], places          # nothing to place without a mesh
mesh = make_local_mesh(model_axis=2)
state, losses, seen, hlo, places = run(mesh)
assert [e["args"]["step"] for e in places] == [0, 1, 2], places
np.testing.assert_allclose(losses, ref, rtol=1e-5)
assert ("manual_computation" in hlo) == bool(USE_PALLAS)
assert all(s.spec == P("data", None) for s in seen), seen
w = state["params"]["units"]["0_mamba"]["in_proj"]
assert w.sharding.spec == P(None, "data", "model"), w.sharding
m = state["opt"].m["units"]["0_mamba"]["in_proj"]
assert m.sharding.spec == w.sharding.spec, m.sharding
assert len(w.sharding.device_set) == 4
print("MESH_OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MESH_OK" in out.stdout, out.stderr[-2000:]
