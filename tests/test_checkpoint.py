"""Checkpoint: roundtrip (incl. bf16), atomic publish, keep-K GC, template
restore with dtype/shape checks, resume metadata."""
import os
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint.checkpoint import CheckpointManager


def _tree(rng):
    return {"params": {"w": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32),
                       "emb": jnp.asarray(rng.normal(size=(8, 2)),
                                          jnp.bfloat16)},
            "opt": {"m": jnp.zeros((4, 3)), "step": jnp.asarray(7)}}


def test_roundtrip(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    tree = _tree(rng)
    mgr.save(10, tree, meta={"step": 10, "note": "x"})
    assert mgr.latest_step() == 10
    got = mgr.restore(tree)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))
    assert mgr.read_meta(10)["meta"]["note"] == "x"


def test_keep_k_gc(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    tree = _tree(rng)
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    assert mgr.all_steps() == [3, 4]


def test_async_save(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
    tree = _tree(rng)
    mgr.save(5, tree)
    mgr.wait()
    assert mgr.latest_step() == 5
    got = mgr.restore(tree)
    np.testing.assert_allclose(got["params"]["w"], tree["params"]["w"])


def test_no_partial_checkpoint_visible(tmp_path, rng):
    """tmp dirs are never listed as checkpoints."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    os.makedirs(tmp_path / "step_99.tmp")
    assert mgr.all_steps() == []
    mgr.save(1, _tree(rng))
    assert mgr.all_steps() == [1]


def test_restore_missing_leaf_raises(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"a": jnp.zeros(2)})
    with pytest.raises(KeyError):
        mgr.restore({"a": jnp.zeros(2), "b": jnp.zeros(3)})


def test_async_save_error_propagates(tmp_path, rng, monkeypatch):
    """A failed background write must surface at the next sync point —
    wait() or the following save() — not vanish with the daemon thread."""
    mgr = CheckpointManager(str(tmp_path), async_save=True)

    def boom(*a, **k):
        raise OSError("disk full (injected)")

    monkeypatch.setattr(np, "savez", boom)
    mgr.save(1, {"a": jnp.zeros(2)})
    with pytest.raises(RuntimeError, match="disk full"):
        mgr.wait()
    monkeypatch.undo()
    # the manager is usable again once the error has been delivered
    mgr.save(2, {"a": jnp.zeros(2)})
    mgr.wait()
    assert mgr.latest_step() == 2


def test_async_save_error_raises_on_next_save(tmp_path, rng, monkeypatch):
    mgr = CheckpointManager(str(tmp_path), async_save=True)

    def boom(*a, **k):
        raise OSError("quota exceeded (injected)")

    monkeypatch.setattr(np, "savez", boom)
    mgr.save(1, {"a": jnp.zeros(2)})
    with pytest.raises(RuntimeError, match="quota exceeded"):
        mgr.save(2, {"a": jnp.zeros(2)})   # save() syncs via wait() first


def test_restore_corrupt_arrays_clear_error(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(3, {"a": jnp.zeros(2)})
    with open(tmp_path / "step_3" / "arrays.npz", "wb") as f:
        f.write(b"this is not an npz archive")
    with pytest.raises(ValueError, match="corrupt"):
        mgr.restore({"a": jnp.zeros(2)}, step=3)


def test_restore_missing_arrays_file_clear_error(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(4, {"a": jnp.zeros(2)})
    os.remove(tmp_path / "step_4" / "arrays.npz")
    with pytest.raises(FileNotFoundError, match="no arrays.npz"):
        mgr.restore({"a": jnp.zeros(2)}, step=4)


def test_read_meta_unpublished_step_clear_error(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"a": jnp.zeros(2)})
    with pytest.raises(FileNotFoundError, match="never published"):
        mgr.read_meta(99)


def test_restore_template_by_shape_struct(tmp_path, rng):
    """Restore into eval_shape templates (how the trainer resumes) and cast
    dtype when the template asks for it (elastic precision change)."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    tree = _tree(rng)
    mgr.save(2, tree)
    tpl = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
    got = mgr.restore(tpl)
    np.testing.assert_allclose(np.asarray(got["params"]["emb"], np.float32),
                               np.asarray(tree["params"]["emb"], np.float32))


def test_elastic_restore_across_mesh_sizes():
    """Save under a (4,2) mesh, restore under (2,4) — checkpoints are
    mesh-agnostic (host arrays) and device_put resharded on load."""
    import subprocess
    import sys
    src = r"""
import tempfile, numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.checkpoint import CheckpointManager
from jax.sharding import AxisType

d = tempfile.mkdtemp()
mesh1 = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
w1 = jax.device_put(w, NamedSharding(mesh1, P("data", "model")))
mgr = CheckpointManager(d, async_save=False)
mgr.save(1, {"w": w1})

mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
sh2 = {"w": NamedSharding(mesh2, P("data", "model"))}
got = mgr.restore({"w": w}, shardings=sh2)
assert got["w"].sharding == sh2["w"], got["w"].sharding
np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(w))
print("ELASTIC_OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "ELASTIC_OK" in out.stdout, out.stderr[-1500:]
