"""Head-structured (Mamba-2 / SSD) selective scan: parity, resets, decode.

Acceptance surface of the single-matmul blocked path:
  * ``selective_scan_heads(method='blocked')`` fwd + grads vs the sequential
    per-head reference, random packed resets, chunk not dividing L, h0 carry
  * the Pallas ``schedule='blocked_heads'`` kernels (interpret mode)
    fwd + grads vs the same reference
  * packed-reset boundary rule: gradients never cross a pos==0 boundary
  * Mamba-1 degenerate dispatch: ``selective_scan`` ≡ heads with dh = 1
  * mamba2 block: single-token ``step_`` decode == full-sequence apply
  * structural memory claim: no (B, L, H, dh, N) trajectory in the jaxpr
  * the dual (C·Bᵀ) in-chunk form: fwd, grads, carried and segment-end
    states against the sequential reference, XLA and Pallas
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import ssm as core_ssm
from repro.kernels import ops as kops
from repro.kernels.ops import selective_scan_heads as kops_heads


def _packed_pos(rng, Bz, L, max_cuts=3):
    """Random packed position ids; cuts straddle power-of-two chunks."""
    pos = np.zeros((Bz, L), np.int32)
    for b in range(Bz):
        cuts = sorted(rng.choice(np.arange(1, L),
                                 size=min(max_cuts, L - 1),
                                 replace=False)) if L > 2 else []
        prev = 0
        for c in list(cuts) + [L]:
            pos[b, prev:c] = np.arange(c - prev)
            prev = c
    return jnp.asarray(pos)


def _heads_inputs(rng, Bz, L, H, P, N):
    u = jnp.asarray(rng.normal(size=(Bz, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.5, (Bz, L, H)), jnp.float32)
    A = -jnp.exp(jnp.asarray(rng.normal(size=(H,)), jnp.float32))
    Bm = jnp.asarray(rng.normal(size=(Bz, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(Bz, L, N)), jnp.float32)
    Dk = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    return u, dt, A, Bm, Cm, Dk, _packed_pos(rng, Bz, L)


# ---------------------------------------------------------------------------
# XLA blocked heads path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Bz,L,H,P,N,T", [(2, 24, 3, 4, 5, 8),
                                          (1, 17, 2, 1, 3, 8),
                                          (1, 64, 4, 8, 16, 16)])
def test_blocked_heads_fwd(rng, Bz, L, H, P, N, T):
    u, dt, A, Bm, Cm, Dk, pos = _heads_inputs(rng, Bz, L, H, P, N)
    y_seq, h_seq = core_ssm.selective_scan_heads(
        u, dt, A, Bm, Cm, Dk, pos, method="sequential", return_state=True)
    y_blk, h_blk = core_ssm.selective_scan_heads(
        u, dt, A, Bm, Cm, Dk, pos, method="blocked", chunk=T,
        return_state=True)
    np.testing.assert_allclose(np.asarray(y_blk), np.asarray(y_seq),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_blk), np.asarray(h_seq),
                               atol=1e-4, rtol=1e-4)


def test_blocked_heads_grads(rng):
    Bz, L, H, P, N, T = 2, 24, 3, 4, 5, 8
    u, dt, A, Bm, Cm, Dk, pos = _heads_inputs(rng, Bz, L, H, P, N)

    def grads(method):
        def f(u, dt, A, Bm, Cm, Dk):
            y = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                              method=method, chunk=T)
            return (y ** 2).sum()
        return jax.grad(f, argnums=tuple(range(6)))(u, dt, A, Bm, Cm, Dk)

    for name, a, b in zip("u dt A B C D".split(), grads("sequential"),
                          grads("blocked")):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3,
                                   err_msg=f"grad {name}")


def test_blocked_heads_h0_carry(rng):
    """Split-pack state carry: scan [x1; x2] == scan x2 with h0 from x1."""
    Bz, L, H, P, N = 1, 20, 2, 3, 4
    u, dt, A, Bm, Cm, Dk, _ = _heads_inputs(rng, Bz, L, H, P, N)
    pos = jnp.tile(jnp.arange(1, L + 1, dtype=jnp.int32), (Bz, 1))  # no reset
    y_all, h_all = core_ssm.selective_scan_heads(
        u, dt, A, Bm, Cm, Dk, pos, method="blocked", chunk=8,
        return_state=True)
    _, h_mid = core_ssm.selective_scan_heads(
        u[:, :11], dt[:, :11], A, Bm[:, :11], Cm[:, :11], Dk, pos[:, :11],
        method="sequential", return_state=True)
    y_rest, h_end = core_ssm.selective_scan_heads(
        u[:, 11:], dt[:, 11:], A, Bm[:, 11:], Cm[:, 11:], Dk, pos[:, 11:],
        h0=h_mid, method="blocked", chunk=4, return_state=True)
    np.testing.assert_allclose(np.asarray(y_rest), np.asarray(y_all[:, 11:]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_end), np.asarray(h_all),
                               atol=1e-4, rtol=1e-4)


def test_blocked_heads_grad_does_not_cross_boundary(rng):
    """Backward PUI (paper §3.4) on the head-structured path."""
    Bz, L, H, P, N = 1, 16, 2, 3, 4
    u, dt, A, Bm, Cm, Dk, _ = _heads_inputs(rng, Bz, L, H, P, N)
    pos = jnp.concatenate([jnp.arange(8), jnp.arange(8)])[None]

    def loss(u_in):
        y = core_ssm.selective_scan_heads(u_in, dt, A, Bm, Cm, Dk, pos,
                                          method="blocked", chunk=8)
        return (y[:, 8:] ** 2).sum()

    g = jax.grad(loss)(u)
    np.testing.assert_allclose(g[:, :8], 0.0, atol=1e-7)
    assert float(jnp.abs(g[:, 8:]).max()) > 0


def test_mamba1_degenerate_dispatch(rng):
    """selective_scan (per-channel) ≡ selective_scan_heads with dh = 1."""
    Bz, L, Dm, N = 2, 24, 6, 4
    u = jnp.asarray(rng.normal(size=(Bz, L, Dm)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.5, (Bz, L, Dm)), jnp.float32)
    A = -jnp.exp(jnp.asarray(rng.normal(size=(Dm, N)), jnp.float32))
    Bm = jnp.asarray(rng.normal(size=(Bz, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(Bz, L, N)), jnp.float32)
    Dk = jnp.asarray(rng.normal(size=(Dm,)), jnp.float32)
    pos = _packed_pos(rng, Bz, L)
    y_flat = core_ssm.selective_scan(u, dt, A, Bm, Cm, Dk, pos,
                                     method="blocked", chunk=8)
    y_heads = core_ssm.selective_scan_heads(u[..., None], dt, A, Bm, Cm, Dk,
                                            pos, method="blocked", chunk=8)
    np.testing.assert_allclose(np.asarray(y_heads[..., 0]),
                               np.asarray(y_flat), atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError):
        core_ssm.selective_scan_heads(
            jnp.repeat(u[..., None], 2, -1), dt, A, Bm, Cm, Dk, pos)


# ---------------------------------------------------------------------------
# Pallas blocked_heads kernels (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Bz,L,H,P,N", [(2, 24, 3, 4, 5), (1, 33, 2, 8, 16)])
def test_pallas_blocked_heads_fwd(rng, Bz, L, H, P, N):
    u, dt, A, Bm, Cm, Dk, pos = _heads_inputs(rng, Bz, L, H, P, N)
    y_ref = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                          method="sequential")
    y = kops_heads(u, dt, A, Bm, Cm, Dk, pos, backend="pallas", chunk=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)


def test_pallas_blocked_heads_grads(rng):
    Bz, L, H, P, N = 2, 24, 3, 4, 5
    u, dt, A, Bm, Cm, Dk, pos = _heads_inputs(rng, Bz, L, H, P, N)

    def lp(*args):
        return (kops_heads(*args, pos, backend="pallas", chunk=8) ** 2).sum()

    def lr(*args):
        return (core_ssm.selective_scan_heads(
            *args, pos, method="sequential") ** 2).sum()

    gp = jax.grad(lp, argnums=tuple(range(6)))(u, dt, A, Bm, Cm, Dk)
    gr = jax.grad(lr, argnums=tuple(range(6)))(u, dt, A, Bm, Cm, Dk)
    for name, a, b in zip("u dt A B C D".split(), gp, gr):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3,
                                   err_msg=f"grad {name}")


def test_pallas_blocked_heads_reset_blocks_grad(rng):
    u, dt, A, Bm, Cm, Dk, _ = _heads_inputs(rng, 1, 16, 2, 4, 4)
    pos = jnp.concatenate([jnp.arange(8), jnp.arange(8)])[None]

    def loss(u_in):
        y = kops_heads(u_in, dt, A, Bm, Cm, Dk, pos, backend="pallas",
                       chunk=8)
        return (y[:, 8:] ** 2).sum()

    g = jax.grad(loss)(u)
    np.testing.assert_allclose(g[:, :8], 0.0, atol=1e-7)
    assert float(jnp.abs(g[:, 8:]).max()) > 0


# ---------------------------------------------------------------------------
# mamba2 block: decode vs full-sequence parity (packed-aware resets)
# ---------------------------------------------------------------------------

def _smoke_cfg():
    from repro.configs.base import get_config
    return dataclasses.replace(get_config("mamba2-370m").reduced(),
                               dtype="float32", d_state=8)


def test_mamba2_step_matches_apply(rng):
    from repro.models import blocks as B
    cfg = _smoke_cfg()
    key = jax.random.PRNGKey(0)
    p = B.init_mamba2(key, cfg)
    Bz, L = 2, 12
    x = jnp.asarray(rng.normal(size=(Bz, L, cfg.d_model)), jnp.float32)
    # packed rows: a reset mid-row exercises the packed-aware decode reset
    pos = np.concatenate([np.arange(5), np.arange(L - 5)])
    pos = jnp.tile(jnp.asarray(pos, jnp.int32)[None], (Bz, 1))
    ctx = B.Ctx(positions=pos,
                segment_ids=jnp.ones((Bz, L), jnp.int32))
    y_full = B.apply_mamba2(p, x, ctx, cfg)
    cache = B.init_mamba2_cache(cfg, Bz, jnp.float32)
    ys = []
    for t in range(L):
        sctx = B.Ctx(reset_t=pos[:, t] == 0)
        y_t, cache = B.step_mamba2(p, x[:, t:t + 1], cache, sctx, cfg)
        ys.append(y_t)
    y_step = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full),
                               atol=2e-5, rtol=1e-4)


def test_mamba2_sharding_rules():
    """Head-structured param leaves pattern-match into PartitionSpecs."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd

    class FakeMesh:
        def __init__(self, **axes):
            self.shape = axes

    mesh = FakeMesh(data=4, model=2)
    assert shd._param_rule("bc_proj", (128, 32), mesh) == P("model", "data")
    assert shd._param_rule("dt_proj", (128, 8), mesh) == P("model", None)
    assert shd._param_rule("A_log", (8,), mesh) == P("model")       # mamba2
    assert shd._param_rule("A_log", (128, 16), mesh) == P("model", None)
    # head-structured decode cache: (B, H, dh, N) shards heads over model
    cache = {"ssm": jax.ShapeDtypeStruct((8, 4, 16, 8), jnp.float32)}
    spec = shd.cache_pspecs(cache, mesh, batch_size=8)
    assert spec["ssm"] == P("data", "model", None, None)


# ---------------------------------------------------------------------------
# structural memory claim
# ---------------------------------------------------------------------------

def test_blocked_heads_jaxpr_has_no_full_trajectory():
    """`blocked` heads never materializes the (B, L, H, dh, N) state
    trajectory — only chunk-local (B, T, H, dh, N) slices."""
    Bz, L, H, P, N, T = 1, 512, 4, 8, 16, 32
    args = (jnp.zeros((Bz, L, H, P)), jnp.full((Bz, L, H), 0.1),
            -jnp.ones((H,)), jnp.zeros((Bz, L, N)),
            jnp.zeros((Bz, L, N)), jnp.zeros((H,)),
            jnp.zeros((Bz, L), jnp.int32))

    jaxpr = jax.make_jaxpr(lambda *a: core_ssm.selective_scan_heads(
        *a, method="blocked", chunk=T))(*args)
    want = (Bz, L, H, P, N)

    def subjaxprs(val):
        if isinstance(val, jax.extend.core.Jaxpr):
            yield val
        elif isinstance(val, jax.extend.core.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, (tuple, list)):
            for v in val:
                yield from subjaxprs(v)

    def shapes(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                yield getattr(v.aval, "shape", None)
            for val in eqn.params.values():
                for sub in subjaxprs(val):
                    yield from shapes(sub)

    assert not any(s == want for s in shapes(jaxpr.jaxpr))


# ---------------------------------------------------------------------------
# dual-form vs quad-form parity (XLA + Pallas interpret)
# ---------------------------------------------------------------------------

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

def _dual_inputs(B=2, L=96, H=3, P=16, N=8, seed=3):
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
    u, dt, Bm, Cm, A, Dk, pos = _dual_inputs()
    ref = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                        method="sequential")
    got = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                        method="blocked", chunk=chunk,
                                        intra="dual")
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-5)


def test_dual_grad_parity_xla():
    u, dt, Bm, Cm, A, Dk, pos = _dual_inputs()

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
    u, dt, Bm, Cm, A, Dk, pos = _dual_inputs()
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
    u, dt, Bm, Cm, A, Dk, pos = _dual_inputs()
    ref = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                        method="sequential")
    y = kops.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                  backend="pallas", chunk=32,
                                  schedule="blocked_heads_dual")
    np.testing.assert_allclose(y, ref, atol=2e-5, rtol=1e-5)
    # explicit subtile override
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
    """A sub_t that does not divide the chunk degrades to a valid subtile
    (largest divisor ≤ request), never crashes the trace."""
    from repro.kernels.selective_scan import _pick_subtile
    assert _pick_subtile(32, 7) == 4
    assert _pick_subtile(16, 32) == 16
    u, dt, Bm, Cm, A, Dk, pos = _dual_inputs()
    ref = core_ssm.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                        method="sequential")
    y = kops.selective_scan_heads(u, dt, A, Bm, Cm, Dk, pos,
                                  backend="pallas", chunk=32, sub_t=7)
    np.testing.assert_allclose(y, ref, atol=2e-5, rtol=1e-5)
