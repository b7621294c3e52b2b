"""The plain reference, each document alone, against the program's packed
``LM.loss`` on a small packed batch on the CPU: the PackMamba property."""
import dataclasses

import jax
import numpy as np
import pytest

from bench.lib.harness import load_json
from bench.lib.model import (from_program, init_weights, program_config,
                             sizes, to_program)
from bench.reference import mamba as ref


@pytest.fixture
def small(monkeypatch):
    monkeypatch.setattr(ref, "LENGTH_QUANTUM", 64)
    monkeypatch.setattr(ref, "SCAN_CHUNK", 16)
    monkeypatch.setattr(ref, "LOSS_CHUNK", 32)
    conf = dict(load_json("configs", "mamba-110m"), d_model=32, n_layer=2,
                vocab_size=61, pad_vocab_size_multiple=8,
                ssm_cfg={"d_state": 8, "d_conv": 4, "expand": 2,
                         "dt_rank": "auto"})
    return conf


def _packed(docs, rows, L):
    from repro.core.packing import pack
    pb = pack(docs, L, num_rows=rows)
    return {"tokens": pb.tokens, "positions": pb.positions,
            "segment_ids": pb.segment_ids}


def test_reference_matches_packed_loss_per_document(small):
    conf = small
    s = sizes(conf)
    rng = np.random.default_rng(0)
    docs = [rng.integers(1, s.vocab_real, n).astype(np.int32)
            for n in (5, 17, 40, 9, 30, 2, 23)]
    batch = _packed(docs, 2, 64)
    w = jax.jit(lambda k: init_weights(s, k))(jax.random.PRNGKey(3))
    from repro.models.lm import build_model
    model = build_model(dataclasses.replace(program_config(conf),
                                            dtype="float32"))
    with jax.default_matmul_precision("highest"):
        (lp, _), gp = jax.value_and_grad(model.loss, has_aux=True)(
            to_program(w), batch)
    lr, gr = ref.loss_and_grad(w, docs, s.eps, "f32", max_tokens=256)
    assert abs(float(lp) - lr) < 1e-5 * abs(lr)
    gp = ref.leaf_norms(from_program(gp))
    gr = ref.leaf_norms(gr)
    for k in gr:
        assert abs(gp[k] - gr[k]) <= 1e-4 * gr[k], k


def test_documents_are_independent(small):
    """Packing two documents side by side changes neither's loss."""
    conf = small
    s = sizes(conf)
    rng = np.random.default_rng(1)
    a, b = (rng.integers(1, s.vocab_real, n).astype(np.int32)
            for n in (33, 20))
    w = jax.jit(lambda k: init_weights(s, k))(jax.random.PRNGKey(4))
    la, _ = ref.loss_and_grad(w, [a], s.eps, "f32", 256)
    lb, _ = ref.loss_and_grad(w, [b], s.eps, "f32", 256)
    lab, _ = ref.loss_and_grad(w, [a, b], s.eps, "f32", 256)
    assert lab == pytest.approx((la * 32 + lb * 19) / 51, rel=1e-6)


def test_blocks_keep_every_document_once():
    rng = np.random.default_rng(2)
    docs = [np.arange(1, n + 1, dtype=np.int32)
            for n in rng.integers(57, 2048, 40)]
    got = []
    shapes = set()
    for tok, lens in ref.blocks(docs, 49152):
        shapes.add(tok.shape)
        assert tok.shape[0] * tok.shape[1] <= 49152
        got += [tuple(t[:n]) for t, n in zip(tok, lens) if n]
    assert sorted(got) == sorted(tuple(d) for d in docs)
    assert len(shapes) <= 8
