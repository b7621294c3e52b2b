"""Per-architecture smoke tests (assignment requirement): reduced config per
family, one forward/train step on CPU, output shapes + no NaNs; decode parity
for every stateful family."""
import dataclasses
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import all_names
from repro.configs.base import get_config
from repro.models.lm import build_model
from repro.optim.adamw import AdamW, constant_schedule
from repro.train.trainer import make_train_step

ARCHS = ["recurrentgemma-2b", "stablelm-1.6b", "deepseek-coder-33b",
         "gemma-7b", "deepseek-67b", "hubert-xlarge", "mixtral-8x22b",
         "moonshot-v1-16b-a3b", "qwen2-vl-2b", "xlstm-125m",
         "mamba-110m", "mamba-1.4b", "mamba-2.8b", "mamba2-370m"]


def _batch(rng, cfg, B=2, L=32):
    pos = np.tile(np.concatenate([np.arange(20), np.arange(12)]), (B, 1))
    seg = np.tile(np.concatenate([np.full(20, 1), np.full(12, 2)]), (B, 1))
    batch = {"tokens": jnp.asarray(
        rng.integers(1, cfg.vocab, (B, L)), jnp.int32),
        "positions": jnp.asarray(pos, jnp.int32),
        "segment_ids": jnp.asarray(seg, jnp.int32)}
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, L, cfg.d_model)), jnp.float32)
        batch["labels"] = jnp.asarray(
            rng.integers(0, cfg.vocab, (B, L)), jnp.int32)
    if cfg.family == "vlm":
        batch["mrope_positions"] = jnp.asarray(
            np.repeat(pos[..., None], 3, axis=-1), jnp.int32)
        batch["vision_embeds"] = jnp.asarray(
            rng.normal(size=(B, 4, cfg.d_model)), jnp.float32)
        batch["vision_positions"] = jnp.asarray(
            rng.integers(0, L, (B, 4)), jnp.int32)
    return batch


def test_registry_complete():
    assert set(ARCHS) <= set(all_names())


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_train_step(arch, rng):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(rng, cfg)
    logits = model.forward(params, batch)
    assert logits.shape == (2, 32, cfg.vocab)
    assert not bool(jnp.isnan(logits).any()), "NaN in logits"
    # one full train step (fwd+bwd+AdamW)
    opt = AdamW(constant_schedule(1e-3))
    step = jax.jit(make_train_step(model, opt))
    state = {"params": params, "opt": opt.init(params)}
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    diff = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                        state["params"], params)
    assert max(jax.tree.leaves(diff)) > 0


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "mamba-110m",
                                  "mamba-1.4b", "gemma-7b", "mamba2-370m",
                                  "recurrentgemma-2b", "xlstm-125m",
                                  "mixtral-8x22b", "qwen2-vl-2b"])
def test_decode_matches_forward(arch, rng):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    T = 12
    toks = jnp.asarray(rng.integers(1, cfg.vocab, (1, T)), jnp.int32)
    batch = {"tokens": toks, "positions": jnp.arange(T)[None],
             "segment_ids": jnp.ones((1, T), jnp.int32)}
    if cfg.family == "vlm":
        batch["mrope_positions"] = jnp.repeat(
            jnp.arange(T)[None, :, None], 3, axis=-1)
    full = model.forward(params, batch)
    cache = model.init_cache(1, 16)
    step = jax.jit(model.decode_step)
    errs = []
    for t in range(T):
        kw = {}
        if cfg.mrope_sections is not None:
            kw["mrope_positions"] = jnp.full((1, 1, 3), t, jnp.int32)
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1],
                                      jnp.asarray([t]), **kw)
        errs.append(float(jnp.abs(lg[0] - full[0, t]).max()))
    assert max(errs) < 2e-2, f"{arch}: {errs}"


def test_decode_reset_isolates_sequences(rng):
    """Serving a second sequence after a reset matches a fresh cache — the
    decode-path analogue of PUI."""
    cfg = get_config("mamba-110m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    s1 = jnp.asarray(rng.integers(1, cfg.vocab, (1, 5)), jnp.int32)
    s2 = jnp.asarray(rng.integers(1, cfg.vocab, (1, 4)), jnp.int32)
    # run s1 then reset then s2 in one cache
    cache = model.init_cache(1, 16)
    for t in range(5):
        _, cache = model.decode_step(params, cache, s1[:, t:t + 1],
                                     jnp.asarray([t]))
    out_joint = []
    for t in range(4):
        lg, cache = model.decode_step(
            params, cache, s2[:, t:t + 1], jnp.asarray([t]),
            reset=jnp.asarray([t == 0]))
        out_joint.append(lg)
    # fresh cache for s2 alone
    cache2 = model.init_cache(1, 16)
    out_fresh = []
    for t in range(4):
        lg, cache2 = model.decode_step(params, cache2, s2[:, t:t + 1],
                                       jnp.asarray([t]))
        out_fresh.append(lg)
    for a, b in zip(out_joint, out_fresh):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_prefill_logits_matches_forward(rng):
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, L, n = 2, 16, 11
    toks = jnp.asarray(rng.integers(1, cfg.vocab, (B, L)), jnp.int32)
    seg = jnp.asarray((np.arange(L) < n)[None].repeat(B, 0).astype(np.int32))
    pos = jnp.asarray((np.arange(L) * (np.arange(L) < n))[None]
                      .repeat(B, 0).astype(np.int32))
    batch = {"tokens": toks, "positions": pos, "segment_ids": seg}
    pl = model.prefill_logits(params, batch)
    full = model.forward(params, batch)
    np.testing.assert_allclose(pl, full[:, n - 1], atol=1e-4)


@pytest.mark.parametrize("arch", ["gemma-7b", "recurrentgemma-2b",
                                  "mamba-1.4b"])
def test_tied_embeddings_shape(arch):
    cfg = get_config(arch).reduced()
    assert cfg.tie_embeddings
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert "head" not in params
    assert params["embed"].shape == (cfg.vocab, cfg.d_model)


@pytest.mark.parametrize("arch,scaled", [("gemma-7b", True),
                                         ("recurrentgemma-2b", True),
                                         ("mamba-1.4b", False),
                                         ("mamba-110m", False)])
def test_embedding_scale_follows_published_model(arch, scaled, rng):
    """Gemma-family models multiply the input embedding by sqrt(d_model);
    Mamba, tied or not, takes the table's rows as they are."""
    cfg = get_config(arch).reduced()
    assert cfg.embed_scale is scaled
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (2, 8)), jnp.int32)
    x = model._embed(params, {"tokens": tokens})
    rows = params["embed"][tokens]
    np.testing.assert_array_equal(
        x, rows * (cfg.d_model ** 0.5 if scaled else 1.0))


def _prefill_packed(model, params, tokens):
    B, L = tokens.shape
    batch = {"tokens": tokens, "positions": jnp.tile(jnp.arange(L), (B, 1)),
             "segment_ids": jnp.ones((B, L), jnp.int32)}
    ends = jnp.full((B, 1), L - 1, jnp.int32)
    return model.prefill_packed(params, batch, 16, ends)[0][:, 0]


def _prefill_chunk(model, params, tokens):
    B, L = tokens.shape
    batch = {"tokens": tokens, "positions": jnp.tile(jnp.arange(L), (B, 1)),
             "segment_ids": jnp.ones((B, L), jnp.int32)}
    return model.prefill_chunk(params, model.init_cache(B, 16), batch,
                               jnp.zeros((B,), jnp.int32))[0]


def _decode(model, params, tokens):
    cache = model.init_cache(tokens.shape[0], 16)
    for t in range(tokens.shape[1]):
        logits, cache = model.decode_step(params, cache, tokens[:, t:t + 1],
                                          jnp.full((tokens.shape[0],), t))
    return logits


def _forward(model, params, tokens):
    B, L = tokens.shape
    return model.forward(params, {
        "tokens": tokens, "positions": jnp.tile(jnp.arange(L), (B, 1)),
        "segment_ids": jnp.ones((B, L), jnp.int32)})[:, -1]


@pytest.mark.parametrize("entry", [_forward, _decode, _prefill_packed,
                                   _prefill_chunk])
def test_tied_mamba_is_untied_with_head_copied(entry, rng):
    """Every path that embeds treats a tied Mamba as the untied model whose
    head holds the embedding's transpose: no path scales the input."""
    cfg = get_config("mamba-1.4b").reduced()
    tied = build_model(cfg)
    untied = build_model(dataclasses.replace(cfg, tie_embeddings=False))
    params = tied.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(rng.integers(1, cfg.vocab, (2, 6)), jnp.int32)
    got = entry(tied, params, tokens)
    want = entry(untied, dict(params, head=params["embed"].T), tokens)
    # the same float32 arithmetic; only the head operand's layout may
    # differ (a transposed view against a copy), which can reorder the
    # head's sums: 1e-6 is that rounding, a scaled input moves it by O(1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_pattern_units():
    cfg = get_config("recurrentgemma-2b")
    from repro.models.lm import unit_layout
    names = [k for k, _ in unit_layout(cfg)]
    assert names == ["0_rec", "0_ffn", "1_rec", "1_ffn", "2_attn", "2_ffn"]
    model = build_model(cfg)
    assert model.n_units == 8 and model.n_tail == 2   # 26 = 8×3 + 2
    cfg2 = get_config("xlstm-125m")
    model2 = build_model(cfg2)
    assert model2.n_units == 2 and model2.n_tail == 0


@pytest.mark.parametrize("arch,use_pallas,scan_dtype,platform,want", [
    ("mamba-110m", None, "float32", "tpu", "pallas"),
    ("mamba-110m", None, "float32", "cpu", "xla"),
    ("mamba-110m", None, "bfloat16", "tpu", "xla"),
    ("mamba2-370m", None, "float32", "tpu", "xla"),
    ("mamba-110m", True, "float32", "cpu", "pallas"),
    ("mamba2-370m", True, "bfloat16", "cpu", "pallas"),
    ("mamba-110m", False, "float32", "tpu", "xla"),
])
def test_scan_backend(monkeypatch, arch, use_pallas, scan_dtype, platform,
                      want):
    """The sequence scan's backend from the decay's form (per-channel for
    Mamba-1, per-head scalar for Mamba-2), ``use_pallas``, ``scan_dtype``
    and the platform traced for: the table of ``_scan_backend``."""
    from repro.models.blocks import _scan_backend
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    cfg = dataclasses.replace(get_config(arch), use_pallas=use_pallas,
                              scan_dtype=scan_dtype)
    A = jnp.zeros((cfg.d_inner, cfg.d_state) if cfg.ssm_variant == "mamba1"
                  else (cfg.n_ssm_heads,))
    assert _scan_backend(cfg, A) == want
