"""Tests for the shape-keyed autotuning subsystem (repro/tune) and the
dual-form in-chunk evaluator it tunes over.

Covers (ISSUE 4): cache round-trip; fingerprint mismatch forcing a re-tune;
bucketed + nearest-key lookup; ``scan_tune="off"`` tracing identically to
the hard-coded defaults (and never consulting the tuner); dual-vs-quad
fwd/grad parity against the sequential reference on both the XLA path and
the Pallas (interpret) kernels; the shared timing helper's injectable
clock; the runner sweep; and the perf/config override mapping.
"""
import dataclasses
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core import ssm as core_ssm
from repro.kernels import ops as kops
from repro.models.lm import build_model
from repro.tune import (ShapeKey, TuneCache, shape_key, space_for, tuned,
                        tuned_config_overrides, l_bucket, reset_bucket)
from repro.tune import cache as tcache
from repro.tune import runner as trunner

FP_A = {"schema": 1, "device_kind": "cpu", "platform": "cpu", "jax": "1"}
FP_B = {"schema": 1, "device_kind": "v5e", "platform": "tpu", "jax": "1"}


@pytest.fixture(autouse=True)
def _fresh_cache_registry():
    tcache.reset_caches()
    yield
    tcache.reset_caches()


# ---------------------------------------------------------------------------
# shape keys + buckets
# ---------------------------------------------------------------------------

def test_l_bucket_and_reset_bands():
    assert l_bucket(1) == 16
    assert l_bucket(256) == 256
    assert l_bucket(300) == 512
    assert reset_bucket(0.0) == "none"
    assert reset_bucket(1 / 1000) == "sparse"
    assert reset_bucket(1 / 100) == "mid"
    assert reset_bucket(0.5) == "dense"
    assert reset_bucket(None) == "mid"      # packed, density unknown


def test_shape_key_encode_roundtrip():
    k = shape_key("selective_scan_heads", B=2, L=300, H=4, dh=64, N=16)
    assert k.Lb == 512
    assert ShapeKey.decode(k.encode()) == k


def test_shape_key_objective_roundtrip_and_legacy_decode():
    """fwdbwd keys append a 10th field; fwd keys encode byte-identically
    to the 9-field pre-objective format (committed caches stay valid) and
    9-field strings decode as objective='fwd'."""
    kf = shape_key("selective_scan", B=1, L=256, D=64, N=8)
    kb = shape_key("selective_scan", B=1, L=256, D=64, N=8,
                   objective="fwdbwd")
    assert kf.objective == "fwd"
    assert kf.encode().count("|") == 8            # legacy 9-field format
    assert kb.encode() == kf.encode() + "|fwdbwd"
    assert ShapeKey.decode(kb.encode()) == kb
    assert ShapeKey.decode(kf.encode()) == kf     # 9 fields -> fwd
    with pytest.raises(ValueError):
        shape_key("selective_scan", B=1, L=256, D=64, N=8,
                  objective="backward-only")


def test_nearest_lookup_never_crosses_objectives():
    """A forward-tuned winner must not be served to a training (fwdbwd)
    query, and vice versa — the schedules optimize different graphs."""
    c = TuneCache(fp=FP_A)
    kf = shape_key("selective_scan", B=1, L=512, D=256, N=16)
    c.put(kf, {"backend": "xla", "method": "associative"}, 10.0)
    near_fwd = shape_key("selective_scan", B=1, L=600, D=256, N=16)
    assert c.lookup(near_fwd)[1] == "nearest"
    near_bwd = shape_key("selective_scan", B=1, L=600, D=256, N=16,
                         objective="fwdbwd")
    assert c.lookup(near_bwd) == (None, None)
    # and a fwdbwd entry resolves for fwdbwd queries only
    kb = shape_key("selective_scan", B=1, L=512, D=256, N=16,
                   objective="fwdbwd")
    c.put(kb, {"backend": "xla", "method": "blocked", "chunk": 64}, 20.0)
    got, how = c.lookup(near_bwd)
    assert how == "nearest" and got["method"] == "blocked"
    assert c.lookup(near_fwd)[0]["method"] == "associative"


def test_runner_fwdbwd_objective_sweeps_and_caches(monkeypatch):
    """The fwdbwd thunk (jit value_and_grad over the candidate scan) runs,
    and ensure() keys the measurement under the objective-tagged entry."""
    monkeypatch.setattr(
        trunner, "space_for",
        lambda key, include_pallas=False: [
            {"backend": "xla", "method": "blocked", "chunk": 16,
             "intra": "quad"},
            {"backend": "xla", "method": "sequential"},
        ])
    c = TuneCache()
    assert trunner.ensure("selective_scan_heads", B=1, L=64, H=2, dh=8,
                          N=4, cache=c, rounds=1, objective="fwdbwd")
    kb = shape_key("selective_scan_heads", B=1, L=64, H=2, dh=8, N=4,
                   objective="fwdbwd")
    assert kb.encode() in c.entries
    # the forward entry is untouched -> a fwd ensure() measures separately
    kf = shape_key("selective_scan_heads", B=1, L=64, H=2, dh=8, N=4)
    assert kf.encode() not in c.entries
    assert trunner.ensure("selective_scan_heads", B=1, L=64, H=2, dh=8,
                          N=4, cache=c, rounds=1)
    assert kf.encode() in c.entries
    # cached -> no re-measure
    assert trunner.ensure("selective_scan_heads", B=1, L=64, H=2, dh=8,
                          N=4, cache=c, objective="fwdbwd") is False


def test_space_bounded_and_has_dual():
    k = shape_key("selective_scan_heads", B=1, L=1024, H=2, dh=128, N=16)
    cands = space_for(k)
    assert 0 < len(cands) <= 16
    intras = {c.get("intra") for c in cands}
    assert {"quad", "dual"} <= intras
    # xla-only unless pallas explicitly included
    assert all(c["backend"] == "xla" for c in cands)
    assert any(c["backend"] == "pallas"
               for c in space_for(k, include_pallas=True))


# ---------------------------------------------------------------------------
# cache: round-trip, fingerprint, nearest-key
# ---------------------------------------------------------------------------

def test_cache_roundtrip(tmp_path):
    c = TuneCache(fp=FP_A)
    k = shape_key("selective_scan", B=1, L=256, D=64, N=8)
    c.put(k, {"backend": "xla", "method": "blocked", "chunk": 32}, 123.4,
          candidates=5)
    p = c.save(str(tmp_path / "tc.json"))
    c2 = TuneCache.load(p, fp=FP_A)
    assert not c2.stale
    knobs, how = c2.lookup(k)
    assert how == "exact"
    assert knobs == {"backend": "xla", "method": "blocked", "chunk": 32}
    # bucketed: any L in the same power-of-two bucket hits the same entry
    same_bucket = shape_key("selective_scan", B=1, L=200, D=64, N=8)
    assert c2.lookup(same_bucket)[1] == "exact"


def test_fingerprint_mismatch_forces_retune(tmp_path):
    c = TuneCache(fp=FP_A)
    k = shape_key("selective_scan", B=1, L=256, D=64, N=8)
    c.put(k, {"backend": "xla", "method": "fused_seq"}, 50.0)
    p = c.save(str(tmp_path / "tc.json"))
    c2 = TuneCache.load(p, fp=FP_B)          # other device kind
    assert c2.stale and not c2.entries and c2.stale_entries
    assert c2.lookup(k) == (None, None)      # never serves stale knobs
    # tuned() therefore falls back to the caller's defaults
    kn = tuned("selective_scan", B=1, L=256, D=64, N=8, cache=c2,
               default={"method": "blocked"})
    assert kn == {"method": "blocked"}


def test_save_preserves_foreign_entries(tmp_path):
    """Round-tripping a shared cache file through a foreign machine must
    not destroy the original machine's measurements."""
    p = str(tmp_path / "tc.json")
    k_a = shape_key("selective_scan", B=1, L=256, D=64, N=8)
    k_b = shape_key("selective_scan", B=1, L=512, D=64, N=8)
    a = TuneCache(fp=FP_A)
    a.put(k_a, {"backend": "xla", "method": "blocked", "chunk": 32}, 10.0)
    a.save(p)
    # machine B: A's entries quarantined, B tunes its own and saves
    b = TuneCache.load(p, fp=FP_B)
    assert b.stale and b.lookup(k_a) == (None, None)
    b.put(k_b, {"backend": "xla", "method": "fused_seq"}, 20.0)
    b.save(p)
    # back on machine A: its entry is resurrected, B's is quarantined
    a2 = TuneCache.load(p, fp=FP_A)
    knobs, how = a2.lookup(k_a)
    assert how == "exact" and knobs["chunk"] == 32
    assert a2.stale_entries and a2.lookup(k_b, nearest=False) == (None, None)
    # and on machine B again, B's entry survives too
    b2 = TuneCache.load(p, fp=FP_B)
    assert b2.lookup(k_b)[1] == "exact"


def test_nearest_key_fallback_never_blocks():
    c = TuneCache(fp=FP_A)
    k512 = shape_key("selective_scan", B=1, L=512, D=256, N=16)
    k4k = shape_key("selective_scan", B=1, L=4096, D=256, N=16)
    c.put(k512, {"backend": "xla", "method": "associative"}, 10.0)
    c.put(k4k, {"backend": "xla", "method": "blocked", "chunk": 128}, 99.0)
    # unseen shape resolves to the closest key of the same op
    got, how = c.lookup(shape_key("selective_scan", B=1, L=3000, D=512,
                                  N=16))
    assert how == "nearest" and got["method"] == "blocked"
    got, how = c.lookup(shape_key("selective_scan", B=1, L=600, D=256,
                                  N=16))
    assert how == "nearest" and got["method"] == "associative"
    # but never across ops
    assert c.lookup(shape_key("selective_scan_heads", B=1, L=512, H=4,
                              dh=64, N=16)) == (None, None)
    # and never across the distance cutoff: regime-gated winners (here
    # 'associative', offered only at short L) must not be served to a
    # far-away shape — beyond max_distance the lookup misses cleanly
    assert c.lookup(shape_key("selective_scan", B=1, L=32768, D=256,
                              N=16)) == (None, None)


def test_tuned_merges_over_defaults():
    c = TuneCache(fp=FP_A)
    k = shape_key("selective_scan_heads", B=1, L=256, H=4, dh=16, N=8)
    c.put(k, {"backend": "xla", "method": "blocked", "intra": "dual"}, 5.0)
    kn = tuned("selective_scan_heads", B=1, L=256, H=4, dh=16, N=8,
               cache=c, default={"method": "blocked", "chunk": 64})
    assert kn == {"backend": "xla", "method": "blocked", "chunk": 64,
                  "intra": "dual"}


def test_cache_check_cli(tmp_path, capsys):
    p = str(tmp_path / "tc.json")
    c = TuneCache()                          # real current fingerprint
    c.put(shape_key("selective_scan", B=1, L=64, D=8, N=4),
          {"backend": "xla", "method": "fused_seq"}, 1.0)
    c.save(p)
    import sys
    argv = sys.argv
    try:
        sys.argv = ["cache.py", "--check", p]
        tcache._main()
        assert "OK" in capsys.readouterr().out
        # stale file: rewrite with a foreign fingerprint
        doc = json.load(open(p))
        doc["fingerprint"]["device_kind"] = "not-this-machine"
        json.dump(doc, open(p, "w"))
        tcache._main()
        assert "STALE" in capsys.readouterr().out
        sys.argv = ["cache.py", "--check", str(tmp_path / "absent.json")]
        with pytest.raises(SystemExit):
            tcache._main()
    finally:
        sys.argv = argv


# ---------------------------------------------------------------------------
# scan_tune="off" is bit-identical and never consults the tuner
# ---------------------------------------------------------------------------

def _tiny_cfg(**kw):
    return dataclasses.replace(get_config("mamba-110m").reduced(),
                               n_layers=2, **kw)


def _fwd_jaxpr(cfg, monkeypatch=None):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    L = 32
    batch = {"tokens": jnp.asarray(rng.integers(1, cfg.vocab, (2, L)),
                                   jnp.int32),
             "positions": jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                           (2, L)) % 20,
             "segment_ids": jnp.ones((2, L), jnp.int32)}
    return str(jax.make_jaxpr(model.forward)(params, batch))


def test_scan_tune_off_never_consults_tuner(monkeypatch, tmp_path):
    import repro.tune
    def boom(*a, **k):
        raise AssertionError("tuner consulted with scan_tune='off'")
    monkeypatch.setattr(repro.tune, "tuned", boom)
    _fwd_jaxpr(_tiny_cfg(scan_tune="off"))          # must not raise


@pytest.mark.parametrize("variant", ["mamba1", "mamba2"])
def test_scan_tune_off_jaxpr_identical_to_defaults(variant, tmp_path,
                                                   monkeypatch):
    """off == auto-with-empty-cache (defaults served on miss) == the
    pre-tuner trace; a cache entry then actually changes the schedule."""
    monkeypatch.setenv(tcache.ENV_PATH, str(tmp_path / "tc.json"))
    tcache.reset_caches()
    kw = {} if variant == "mamba1" else {"ssm_variant": "mamba2",
                                         "ssm_head_dim": 16}
    off = _fwd_jaxpr(_tiny_cfg(scan_tune="off", **kw))
    auto_empty = _fwd_jaxpr(_tiny_cfg(scan_tune="auto", **kw))
    assert off == auto_empty
    # now cache a different winner for this op → the trace must change
    cfg = _tiny_cfg(scan_tune="auto", **kw)
    c = tcache.get_cache()
    if variant == "mamba1":
        c.put(shape_key("selective_scan", B=2, L=32, D=cfg.d_inner,
                        N=cfg.d_state),
              {"backend": "xla", "method": "fused_seq"}, 1.0)
    else:
        c.put(shape_key("selective_scan_heads", B=2, L=32,
                        H=cfg.n_ssm_heads, dh=cfg.ssm_hd, N=cfg.d_state),
              {"backend": "xla", "method": "blocked", "chunk": 16,
               "intra": "dual"}, 1.0)
    assert _fwd_jaxpr(cfg) != off


def test_heads_default_intra_is_quad_jaxpr():
    """intra=None must trace exactly as the historical (quad) path."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 0.5, (1, 64, 4)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(1, 64, 8)), jnp.float32)
    A = -jnp.exp(jnp.asarray(rng.normal(size=(4,)), jnp.float32))
    f = lambda intra: str(jax.make_jaxpr(
        lambda u, dt, Bm: core_ssm.selective_scan_heads(
            u, dt, A, Bm, Bm, None, method="blocked", chunk=32,
            intra=intra))(u, dt, Bm))
    assert f(None) == f("quad")
    assert f(None) != f("dual")


# ---------------------------------------------------------------------------
# dual-form vs quad-form parity (XLA + Pallas interpret)
# ---------------------------------------------------------------------------

def _heads_inputs(B=2, L=96, H=3, P=16, N=8, seed=3):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.4, (B, L, H)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    A = -jnp.exp(jnp.asarray(rng.normal(size=(H,)), jnp.float32))
    Dk = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    pos = jnp.asarray(np.concatenate(
        [np.arange(41), np.arange(30), np.arange(L - 71)])[None]
        .repeat(B, 0), jnp.int32)
    return u, dt, Bm, Cm, A, Dk, pos


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_dual_fwd_parity_xla(chunk):
    u, dt, Bm, Cm, A, Dk, pos = _heads_inputs()
    ref = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                        method="sequential")
    got = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                        method="blocked", chunk=chunk,
                                        intra="dual")
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-5)


def test_dual_grad_parity_xla():
    u, dt, Bm, Cm, A, Dk, pos = _heads_inputs()

    def loss(intra):
        def f(u, dt, Bm, Cm):
            kw = dict(method="sequential") if intra == "seq" else \
                dict(method="blocked", chunk=32, intra=intra)
            y = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                              **kw)
            return (y ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2, 3))(u, dt, Bm, Cm)

    for g_d, g_r in zip(loss("dual"), loss("seq")):
        np.testing.assert_allclose(g_d, g_r, atol=2e-3, rtol=1e-4)


def test_dual_state_and_ends_parity():
    """h_last carry + collect_ends handoff match sequential under dual."""
    u, dt, Bm, Cm, A, Dk, pos = _heads_inputs()
    rng = np.random.default_rng(7)
    h0 = jnp.asarray(rng.normal(size=(2, 3, 16, 8)), jnp.float32)
    ends = jnp.asarray([[40, 70, 95, -1], [40, -1, 95, 70]], jnp.int32)
    ref = core_ssm.selective_scan_heads(
        u, dt, A, Bm, Cm, Dk, pos, h0=h0, method="sequential",
        return_state=True, collect_ends=ends)
    got = core_ssm.selective_scan_heads(
        u, dt, A, Bm, Cm, Dk, pos, h0=h0, method="blocked", chunk=32,
        intra="dual", return_state=True, collect_ends=ends)
    for a, b in zip(ref, got):
        np.testing.assert_allclose(b, a, atol=2e-5, rtol=1e-5)


def test_dual_pallas_fwd_and_grad_parity():
    u, dt, Bm, Cm, A, Dk, pos = _heads_inputs()
    ref = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                        method="sequential")
    y = kops.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                  backend="pallas", chunk=32,
                                  schedule="blocked_heads_dual")
    np.testing.assert_allclose(y, ref, atol=2e-5, rtol=1e-5)
    # tuned subtile override
    y8 = kops.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                   backend="pallas", chunk=32,
                                   schedule="blocked_heads_dual", sub_t=8)
    np.testing.assert_allclose(y8, ref, atol=2e-5, rtol=1e-5)

    def loss(fn):
        return jax.grad(lambda u, dt, Bm, Cm: (fn(u, dt, Bm, Cm) ** 2).sum(),
                        argnums=(0, 1, 2, 3))(u, dt, Bm, Cm)

    g_d = loss(lambda u, dt, Bm, Cm: kops.selective_scan_heads(
        u, dt, A, Bm, Cm, Dk, pos, backend="pallas", chunk=32,
        schedule="blocked_heads_dual"))
    g_r = loss(lambda u, dt, Bm, Cm: core_ssm.selective_scan_heads(
        u, dt, A, Bm, Cm, Dk, pos, method="sequential"))
    for a, b in zip(g_d, g_r):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=1e-4)


def test_pallas_non_dividing_sub_t_degrades_not_raises():
    """A tuned sub_t from another L bucket must degrade to a valid subtile
    (largest divisor ≤ request), never crash the trace."""
    from repro.kernels.selective_scan import _pick_subtile
    assert _pick_subtile(32, 7) == 4
    assert _pick_subtile(16, 32) == 16
    u, dt, Bm, Cm, A, Dk, pos = _heads_inputs()
    ref = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                        method="sequential")
    y = kops.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                  backend="pallas", chunk=32, sub_t=7)
    np.testing.assert_allclose(y, ref, atol=2e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# timing helper + runner
# ---------------------------------------------------------------------------

def test_interleaved_min_of_rounds_injectable_clock():
    from benchmarks.timing import interleaved_min_of_rounds
    t = [0.0]
    # fake clock: "a" costs 10us, "b" costs 5us, with a drifty round
    costs = iter([10e-6, 5e-6, 30e-6, 25e-6, 10e-6, 5e-6])

    def clock():
        return t[0]

    calls = {"a": 0, "b": 0}

    def mk(name):
        def thunk():
            calls[name] += 1
            t[0] += next(costs)
            return name
        return thunk

    best, last = interleaved_min_of_rounds(
        [("a", mk("a")), ("b", mk("b"))], rounds=3, warmup=0,
        clock=clock, sync=lambda x: x)
    assert calls == {"a": 3, "b": 3}
    assert best["a"] == pytest.approx(10.0)       # min over rounds, in us
    assert best["b"] == pytest.approx(5.0)
    assert last == {"a": "a", "b": "b"}


def test_runner_tune_key_and_ensure(monkeypatch):
    # shrink the space so the sweep is a smoke test (one real + one broken
    # candidate: the broken one must be dropped, not crash the sweep)
    monkeypatch.setattr(
        trunner, "space_for",
        lambda key, include_pallas=False: [
            {"backend": "xla", "method": "blocked", "chunk": 16,
             "intra": "quad" if key.op == "selective_scan_heads"
             else "assoc"},
            {"backend": "xla", "method": "sequential"},
            {"backend": "xla", "method": "not-a-method"},
        ])
    c = TuneCache()
    k = shape_key("selective_scan_heads", B=1, L=64, H=2, dh=8, N=4)
    knobs = trunner.tune_key(k, cache=c, rounds=1)
    assert knobs["method"] in ("blocked", "sequential")
    rec = c.entries[k.encode()]
    assert rec["candidates"] == 2                 # broken one dropped
    # ensure(): cached key → no re-measure
    assert trunner.ensure("selective_scan_heads", B=1, L=64, H=2, dh=8,
                          N=4, cache=c) is False


def test_runner_reports_every_dropped_candidate(monkeypatch, capsys):
    """A candidate that fails to build is printed and counted
    (``tune.dropped``) even without ``verbose`` — on the chip a Pallas
    kernel that fails to compile must never vanish from a sweep unseen."""
    from repro.obs import Obs
    monkeypatch.setattr(
        trunner, "space_for",
        lambda key, include_pallas=False: [
            {"backend": "xla", "method": "sequential"},
            {"backend": "xla", "method": "not-a-method"},
        ])
    obs = Obs.off()
    k = shape_key("selective_scan", B=1, L=32, D=8, N=4)
    trunner.tune_key(k, cache=TuneCache(), rounds=1, obs=obs)
    out = capsys.readouterr().out
    assert "tune drop" in out and "not-a-method" in out
    assert obs.metrics.counter("tune.dropped").value == 1
    assert obs.metrics.counter("tune.candidates").value == 1


def test_synth_positions_density():
    p = trunner.synth_positions(np.random.default_rng(0), 2, 256, "mid")
    assert p.shape == (2, 256)
    assert int((p == 0).sum(axis=1)[0]) == 256 // 100 + 1
    flat = trunner.synth_positions(np.random.default_rng(0), 1, 64, "none")
    assert int((flat == 0).sum()) == 1


# ---------------------------------------------------------------------------
# config / perf integration
# ---------------------------------------------------------------------------

def test_tuned_config_overrides_mapping():
    c = TuneCache(fp=FP_A)
    cfg = _tiny_cfg(ssm_variant="mamba2", ssm_head_dim=16)
    c.put(shape_key("selective_scan_heads", B=8, L=512, H=cfg.n_ssm_heads,
                    dh=cfg.ssm_hd, N=cfg.d_state),
          {"backend": "xla", "method": "blocked", "chunk": 32,
           "intra": "dual"}, 4.2)
    ov = tuned_config_overrides(cfg, B=8, L=512, cache=c)
    assert ov == {"scan_impl": "blocked", "scan_chunk": 32,
                  "scan_intra": "dual"}
    # pallas winner maps to the kernel-path toggles
    cfg1 = _tiny_cfg()
    c.put(shape_key("selective_scan", B=8, L=512, D=cfg1.d_inner,
                    N=cfg1.d_state, dtype=cfg1.dtype),
          {"backend": "pallas", "schedule": "blocked", "pchunk": 128},
          3.0)
    ov = tuned_config_overrides(cfg1, B=8, L=512, cache=c)
    assert ov == {"use_pallas": True, "pallas_schedule": "blocked"}
    # no scan hot path → no overrides
    assert tuned_config_overrides(get_config("gemma-7b"), B=8, L=512,
                                  cache=c) == {}


def test_model_forward_with_dual_tuned_cache_matches_off(tmp_path,
                                                         monkeypatch):
    """Numerics stay put when the tuner picks a different (valid) schedule:
    a dual-form winner must produce the same logits as the default path."""
    monkeypatch.setenv(tcache.ENV_PATH, str(tmp_path / "tc.json"))
    tcache.reset_caches()
    kw = {"ssm_variant": "mamba2", "ssm_head_dim": 16}
    cfg_off = _tiny_cfg(scan_tune="off", **kw)
    cfg_auto = _tiny_cfg(scan_tune="auto", **kw)
    c = tcache.get_cache()
    c.put(shape_key("selective_scan_heads", B=2, L=32, H=cfg_off.n_ssm_heads,
                    dh=cfg_off.ssm_hd, N=cfg_off.d_state),
          {"backend": "xla", "method": "blocked", "chunk": 16,
           "intra": "dual"}, 1.0)
    model_off, model_auto = build_model(cfg_off), build_model(cfg_auto)
    params = model_off.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    L = 32
    batch = {"tokens": jnp.asarray(rng.integers(1, cfg_off.vocab, (2, L)),
                                   jnp.int32),
             "positions": jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                           (2, L)) % 20,
             "segment_ids": jnp.ones((2, L), jnp.int32)}
    y_off = model_off.forward(params, batch)
    y_auto = model_auto.forward(params, batch)
    np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_off),
                               atol=2e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# compare.py: all offenders in one run
# ---------------------------------------------------------------------------

def test_compare_reports_all_offenders(tmp_path):
    import benchmarks.compare as cmp
    old = [{"op": "s", "shape": "a", "schedule": "x", "us_per_call": 100.0,
            "tok_per_s": 1},
           {"op": "s", "shape": "a", "schedule": "y", "us_per_call": 100.0,
            "tok_per_s": 1},
           {"op": "s", "shape": "a", "schedule": "z", "us_per_call": 100.0,
            "tok_per_s": 1}]
    new = [dict(r, us_per_call=us) for r, us in
           zip(old, (150.0, 95.0, 200.0))]
    po, pn = str(tmp_path / "o.json"), str(tmp_path / "n.json")
    json.dump(old, open(po, "w"))
    json.dump(new, open(pn, "w"))
    lines, offenders = cmp.compare(po, pn, pct=10.0)
    # BOTH regressions reported in one pass, plus the header + ok row
    assert len(offenders) == 2
    assert {o[0] for o in offenders} == {"s/a/x", "s/a/z"}
    assert any("ok" in ln and "s/a/y" in ln for ln in lines)


def test_compare_missing_required_still_reports_other_pairs(tmp_path,
                                                            capsys,
                                                            monkeypatch):
    """A missing required pair fails the gate but must not hide offenders
    in the remaining pairs (one run surfaces everything)."""
    import benchmarks.compare as cmp
    row = {"op": "s", "shape": "a", "schedule": "x", "tok_per_s": 1}
    po, pn = str(tmp_path / "o.json"), str(tmp_path / "n.json")
    json.dump([dict(row, us_per_call=100.0)], open(po, "w"))
    json.dump([dict(row, us_per_call=200.0)], open(pn, "w"))
    absent = str(tmp_path / "absent.json")
    monkeypatch.setattr("sys.argv", ["compare.py", "--pair", absent, absent,
                                     "--pair", po, pn])
    with pytest.raises(SystemExit) as e:
        cmp.main()
    assert e.value.code == 1
    out = capsys.readouterr().out
    assert "MISSING required" in out
    assert "s/a/x" in out and "+100.0%" in out     # 2nd pair still compared
