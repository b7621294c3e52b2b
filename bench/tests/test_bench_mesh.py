"""The four-chip train cell at a CPU size, on four virtual CPU devices
laid out as its (data 2, model 2) mesh: sound, it is correct; with the
timed path broken underneath, it is not. One subprocess runs all three,
since the device count is fixed when JAX starts. Also: the tied program
against the tied reference, and which device ops ``train.collective_ms``
counts, by the op text a device trace prints."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parents[2]
CELL = "train.mamba-1.4b.pack4k.x4"

SCRIPT = r"""
import contextlib, dataclasses, io, json, sys
sys.path[:0] = [ROOT, ROOT + "/src"]
import jax
import numpy as np
from bench.lib import harness as H
from bench.reference import mamba as ref
from repro.configs import base

# at this size bfloat16 rounding alone would read above the cell's limits
ref.LENGTH_QUANTUM, ref.SCAN_CHUNK, ref.LOSS_CHUNK = 64, 16, 32
base.get_config("mamba-1.4b")
base.REGISTRY["mamba-1.4b"] = dataclasses.replace(
    base.REGISTRY["mamba-1.4b"], dtype="float32")
real = H.find_cell(CELL)
conf = dict(real.config, d_model=32, n_layer=2, vocab_size=250,
            ssm_cfg={"d_state": 8, "d_conv": 4, "expand": 2,
                     "dt_rank": "auto"})
t = dict(real.traffic, seq_len=128, rows=4,
         documents=dict(real.traffic["documents"], len_min=8, len_max=60,
                        mu=3.3, sigma=0.6))
cell = H.Cell(CELL, real.kind, real.chips, conf, t, real.end_to_end,
              real.per_layer)
H.find_cell = lambda name: cell
H.OUT = H.Path(OUT)
run = H.load_module(H.BENCH / "run.py")

# the step with its gradient's sum over "data" left out: each data shard
# updates its own slices of the state from its own rows
import jax.numpy as jnp
from bench.drivers import train as T
from jax.sharding import Mesh, PartitionSpec as P
from repro.distributed import sharding as shd
from repro.train.trainer import make_train_step
faulty = T._faulty_step


def unsummed(model, opt):
    real = make_train_step(model, opt)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))

    def step(state, batch):
        n = batch["tokens"].shape[0] // 2
        a, b = (real(state, {k: v[i * n:(i + 1) * n]
                             for k, v in batch.items()}) for i in (0, 1))

        def pick(spec, x, y):
            dims = [i for i, ax in enumerate(spec) if ax == "data"]
            if not dims:
                return x
            h = x.shape[dims[0]] // 2
            return jnp.concatenate(
                [jax.lax.slice_in_dim(x, 0, h, axis=dims[0]),
                 jax.lax.slice_in_dim(y, h, None, axis=dims[0])], dims[0])
        specs = shd.train_state_pspecs(state, mesh)
        return jax.tree.map(pick, specs, a[0], b[0],
                            is_leaf=lambda t: isinstance(t, P)), a[1]
    return step


T._faulty_step = lambda fault, model, opt: (
    unsummed(model, opt) if fault == "data_grads_unsummed"
    else faulty(fault, model, opt))
for fault in (None, "half_batch", "unchanged_state", "data_grads_unsummed"):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                       "--seconds", "1", "--trace", "0"],
                      require_chip=False, fault=fault)
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    print("RESULT", json.dumps({"fault": fault, "rc": rc, "out": out}),
          flush=True)

# the tied reference with its rows and weights spread over the four
# devices, against the same on one device
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from bench.drivers import train_mesh
from bench.lib.model import sizes
from bench.reference import mamba_tied
from repro.distributed import sharding as shd
s = sizes(conf)
rng = np.random.default_rng(1)
docs = [rng.integers(1, s.vocab_real, n).astype(np.int32)
        for n in (5, 17, 40, 9, 30, 2, 23, 60, 11)]
w = jax.jit(lambda k: train_mesh.weights(s, k, True))(jax.random.PRNGKey(5))
got = {}
for shape in ((1, 1), (2, 2)):
    mesh = Mesh(np.array(jax.devices()[:shape[0] * shape[1]]).reshape(shape),
                ("data", "model"))
    specs = train_mesh.bench_weights(shd.param_pspecs(
        train_mesh.program_params(w), mesh))
    wm = jax.tree.map(lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
                      w, specs)
    fn = mamba_tied._nll_and_grad(mesh, mamba_tied.specs_of(wm))
    loss, g = mamba_tied.loss_and_grad(wm, docs, s.eps, "f32", 256, mesh, fn)
    got[shape] = (loss, ref.leaf_norms(g))
(l1, g1), (l4, g4) = got[(1, 1)], got[(2, 2)]
print("REF", json.dumps({"loss": [l1, l4],
                         "grad": {k: [g1[k], g4[k]] for k in g1}}), flush=True)
"""


def test_mesh_cell_correct_and_faults_fail(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = f"ROOT = {str(ROOT)!r}\nCELL = {CELL!r}\n" \
          f"OUT = {str(tmp_path / 'out')!r}\n" + SCRIPT
    proc = subprocess.run([sys.executable, "-c", src], env=env,
                          capture_output=True, text=True, timeout=900)
    got = [json.loads(line[len("RESULT "):])
           for line in proc.stdout.splitlines() if line.startswith("RESULT ")]
    assert len(got) == 4, proc.stderr[-3000:]
    ref = [json.loads(line[len("REF "):]) for line in proc.stdout.splitlines()
           if line.startswith("REF ")]
    assert len(ref) == 1, proc.stderr[-3000:]
    # float32 both; only the order of the sums over rows and chips differs
    (l1, l4), grads = ref[0]["loss"], ref[0]["grad"]
    assert abs(l1 - l4) <= 1e-6 * abs(l1)
    assert set(grads) == {f"['layers']['{k}']" for k in (
        "A_log", "D", "conv1d_bias", "conv1d_weight", "dt_proj_bias",
        "dt_proj_weight", "in_proj", "norm", "out_proj", "x_proj")} | {
        "['embedding']", "['norm_f']"}
    for k, (g1, g4) in grads.items():
        assert abs(g1 - g4) <= 1e-5 * g1, k
    for r in got:
        out = r["out"]
        assert r["rc"] == 0
        assert out["correct"] is (r["fault"] is None), (r["fault"],
                                                        out["checks"])
        assert out["device"]["count"] == 4
        assert out["metrics"]["train_tokens_per_s"]["value"] > 0
        assert out["compiles_in_window"] == 0


def _reader():
    from bench.lib import harness as H
    return H.load_module(H.BENCH / "metrics" / "train.collective_ms.py")


def test_collective_ms_matches_collectives_by_their_op_text():
    m = _reader()
    counted = [
        "%all-gather.70 = bf16[128,2048]{1,0:T(8,128)(2,1)} all-gather("
        "bf16[64,2048]{1,0:T(8,128)(2,1)} %param_0.1209), channel_id=38",
        "%all-reduce.77 = bf16[4,4096,160]{2,1,0} all-reduce(bf16[4,4096,"
        "160]{2,1,0} %get-tuple-element.2643), to_apply=%add.8.clone",
        "%collective-permute-start.1 = (bf16[4,4096,2048]{2,1,0}, bf16[4,"
        "4096,2048]{2,1,0}, u32[], u32[]) collective-permute-start(bf16[4,"
        "4096,2048]{2,1,0} %get-tuple-element.2154)",
        "%collective-permute-done.1 = bf16[4,4096,2048]{2,1,0} "
        "collective-permute-done((bf16[4,4096,2048], bf16[4,4096,2048], "
        "u32[], u32[]) %collective-permute-start.1)",
        "%async-collective-done = bf16[128,2048]{1,0} fusion(u32[] "
        "%get-tuple-element.2644), kind=kCustom, calls=%all-gather.3",
        "%fusion.304 = bf16[1064,4096]{1,0} fusion(bf16[2128,4096]{1,0} "
        "%fusion.303), kind=kCustom, calls=%all-reduce-scatter.1.clone",
        "%all-to-all.9 = bf16[1,2,2,4096,2048]{3,4,2,0,1} all-to-all("
        "bf16[1,2,2,4096,2048]{3,4,2,0,1} %copy.174), dimensions={1}",
        "%reduce-scatter.2 = f32[1024]{0} reduce-scatter(f32[2048]{0} "
        "%add.4), dimensions={0}",
        "%all-gather-start.4 = (bf16[2048,25140], bf16[2048,50280]) "
        "all-gather-start(bf16[2048,25140] %get-tuple-element.2380)",
    ]
    compute = [
        # reads an all-gather's result, moves nothing between chips
        "%fusion.297 = bf16[4,4096,2048]{2,1,0} fusion(bf16[2048,2048]"
        "{1,0} %all-gather.76, bf16[4,4096,2048]{2,1,0} %copy.171), "
        "kind=kOutput, calls=%fused_computation.297",
        "%reduce_sum.529 = bf16[4,4096,16]{2,1,0} reduce(bf16[4,4096,16,"
        "2048] %multiply.1, bf16[] %constant.2), to_apply=%add.9",
        "%branch_0_fun.5 = (f32[4,4096,2048]{2,1,0}) custom-call(f32[4,"
        "4096,2048] %copy.171), custom_call_target=\"tpu_custom_call\"",
        "%all_reduce_like_name_fusion.1 = f32[8]{0} fusion(f32[8]{0} "
        "%p.1), kind=kLoop, calls=%fused_computation.1",
    ]
    assert all(m.is_collective(op) for op in counted)
    assert not any(m.is_collective(op) for op in compute)


def test_collective_ms_reads_nothing_without_a_trace():
    m = _reader()
    run = {"trace": None, "registry_t0": {"train.steps": 0},
           "registry_t1": {"train.steps": 5}}
    assert m.read(run) is None


def test_tied_reference_matches_packed_loss_per_document(monkeypatch):
    """The tied program's packed loss and gradient against
    bench/reference/mamba_tied.py (the head is the unscaled embedding),
    each document alone, its weights on a one-device mesh: what
    bench/tests/test_bench_reference.py checks for the untied model."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bench.drivers import train_mesh
    from bench.lib.harness import load_json
    from bench.lib.model import sizes
    from bench.reference import mamba as ref
    from bench.reference import mamba_tied
    from repro.core.packing import pack
    from repro.models.lm import build_model
    monkeypatch.setattr(ref, "LENGTH_QUANTUM", 64)
    monkeypatch.setattr(ref, "SCAN_CHUNK", 16)
    monkeypatch.setattr(ref, "LOSS_CHUNK", 32)
    conf = dict(load_json("configs", "mamba-1.4b"), d_model=32, n_layer=2,
                vocab_size=61, ssm_cfg={"d_state": 8, "d_conv": 4,
                                        "expand": 2, "dt_rank": "auto"})
    assert conf["tie_embeddings"]
    s = sizes(conf)
    rng = np.random.default_rng(0)
    docs = [rng.integers(1, s.vocab_real, n).astype(np.int32)
            for n in (5, 17, 40, 9, 30, 2, 23)]
    pb = pack(docs, 64, num_rows=2)
    batch = {"tokens": pb.tokens, "positions": pb.positions,
             "segment_ids": pb.segment_ids}
    w = jax.jit(lambda k: train_mesh.weights(s, k, True))(
        jax.random.PRNGKey(3))
    model = build_model(dataclasses.replace(train_mesh.program_config(conf),
                                            dtype="float32"))
    with jax.default_matmul_precision("highest"):
        (lp, _), gp = jax.value_and_grad(model.loss, has_aux=True)(
            train_mesh.program_params(w), batch)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    w = jax.device_put(w, NamedSharding(mesh, P()))
    grad_fn = mamba_tied._nll_and_grad(mesh, mamba_tied.specs_of(w))
    lr, gr = mamba_tied.loss_and_grad(w, docs, s.eps, "f32", 256, mesh,
                                      grad_fn)
    # both float32 with highest matmuls; they differ only in the order of
    # the sums (packed rows against documents alone, blocked loss chunks):
    # round-off of a few ulps on the mean loss, a little more on each
    # gradient norm, whose sums run over every token; the embedding's
    # gradient sums the lookup's and the head's
    assert abs(float(lp) - lr) < 1e-5 * abs(lr)
    gp = ref.leaf_norms(train_mesh.bench_weights(gp))
    gr = ref.leaf_norms(gr)
    assert sorted(gp) == sorted(gr) and "['lm_head']" not in gr
    for k in gr:
        assert abs(gp[k] - gr[k]) <= 1e-4 * gr[k], k
