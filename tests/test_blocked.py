"""Blocked (SSD-style) scan schedule: parity vs the sequential reference.

Covers the acceptance surface of the block-parallel schedule:
  * generic ``scan_blocked`` (cumprod M construction) on random packed
    resets, chunk not dividing L, segments straddling chunk boundaries
  * ``core.ssm.selective_scan(method='blocked')`` fwd + grads, both
    in-chunk evaluators ('matmul' einsum and 'assoc' tree), h0 carry
  * the Pallas scan kernel (interpret mode) fwd + grads, resets on a
    chunk's first row included, bf16 operands, and a packed row equal to
    its documents scanned alone; the backward's row-group rule
  * structural memory claim: no (B, L, D, N) intermediate in the jaxpr
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.scan import segmented_scan
from repro.core import ssm as core_ssm
from repro.kernels.ops import selective_scan as kops_scan
from repro.kernels.ref import selective_scan_ref


def _packed_pos(rng, Bz, L, max_cuts=3):
    """Random packed position ids: segments deliberately straddle chunk
    boundaries (cuts are arbitrary, chunks are powers of two)."""
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


def _ssm_inputs(rng, Bz, L, Dm, N):
    u = jnp.asarray(rng.normal(size=(Bz, L, Dm)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.5, (Bz, L, Dm)), jnp.float32)
    A = -jnp.exp(jnp.asarray(rng.normal(size=(Dm, N)), jnp.float32))
    Bm = jnp.asarray(rng.normal(size=(Bz, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(Bz, L, N)), jnp.float32)
    Dk = jnp.asarray(rng.normal(size=(Dm,)), jnp.float32)
    return u, dt, A, Bm, Cm, Dk, _packed_pos(rng, Bz, L)


# ---------------------------------------------------------------------------
# generic scan_blocked
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,L,D,chunk", [(2, 37, 5, 8), (1, 16, 3, 16),
                                         (3, 64, 4, 5), (1, 7, 2, 32)])
def test_scan_blocked_matches_sequential(rng, B, L, D, chunk):
    a = jnp.asarray(rng.uniform(0.1, 1.0, (B, L, D)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, L, D)), jnp.float32)
    reset = jnp.asarray(rng.random((B, L)) < 0.2)
    h0 = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
    hs, hls = segmented_scan(a, b, reset, h0, method="sequential")
    hb, hlb = segmented_scan(a, b, reset, h0, method="blocked", chunk=chunk)
    np.testing.assert_allclose(hs, hb, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(hls, hlb, atol=1e-5, rtol=1e-5)


def test_scan_blocked_grads(rng):
    B, L, D, chunk = 2, 23, 4, 8
    a = jnp.asarray(rng.uniform(0.1, 1.0, (B, L, D)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, L, D)), jnp.float32)
    reset = jnp.zeros((B, L), bool).at[:, 9].set(True)

    def loss(m):
        def f(a_in, b_in):
            h, _ = segmented_scan(a_in, b_in, reset, method=m, chunk=chunk)
            return (h ** 2).sum()
        return jax.grad(f, argnums=(0, 1))(a, b)

    for ga, gb in zip(loss("sequential"), loss("blocked")):
        np.testing.assert_allclose(ga, gb, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# XLA selective scan, method='blocked'
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("intra", ["matmul", "assoc"])
@pytest.mark.parametrize("Bz,L,Dm,N,T", [(2, 24, 10, 4, 8),
                                         (1, 17, 5, 3, 8),
                                         (1, 64, 16, 16, 16)])
def test_blocked_ssm_fwd(rng, intra, Bz, L, Dm, N, T):
    u, dt, A, Bm, Cm, Dk, pos = _ssm_inputs(rng, Bz, L, Dm, N)
    y_seq = core_ssm.selective_scan(u, dt, A, Bm, Cm, Dk, pos,
                                    method="sequential")
    y_blk, h_blk = core_ssm.selective_scan(u, dt, A, Bm, Cm, Dk, pos,
                                           method="blocked", chunk=T,
                                           intra=intra, return_state=True)
    _, h_seq = core_ssm.selective_scan(u, dt, A, Bm, Cm, Dk, pos,
                                       method="sequential",
                                       return_state=True)
    np.testing.assert_allclose(np.asarray(y_blk), np.asarray(y_seq),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_blk), np.asarray(h_seq),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("intra", ["matmul", "assoc"])
def test_blocked_ssm_grads(rng, intra):
    Bz, L, Dm, N, T = 2, 24, 6, 4, 8
    u, dt, A, Bm, Cm, Dk, pos = _ssm_inputs(rng, Bz, L, Dm, N)

    def grads(method, **kw):
        def f(u, dt, A, Bm, Cm, Dk):
            y = core_ssm.selective_scan(u, dt, A, Bm, Cm, Dk, pos,
                                        method=method, chunk=T, **kw)
            return (y ** 2).sum()
        return jax.grad(f, argnums=tuple(range(6)))(u, dt, A, Bm, Cm, Dk)

    gs = grads("sequential")
    gb = grads("blocked", intra=intra)
    for name, a, b in zip("u dt A B C D".split(), gs, gb):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3,
                                   err_msg=f"{intra} grad {name}")


def test_blocked_ssm_h0_carry(rng):
    """Split-pack state carry: scan [x1; x2] == scan x2 with h0 from x1."""
    Bz, L, Dm, N = 1, 20, 4, 3
    u, dt, A, Bm, Cm, Dk, _ = _ssm_inputs(rng, Bz, L, Dm, N)
    pos = jnp.tile(jnp.arange(1, L + 1, dtype=jnp.int32), (Bz, 1))  # no reset
    y_all, h_all = core_ssm.selective_scan(u, dt, A, Bm, Cm, Dk, pos,
                                           method="blocked", chunk=8,
                                           return_state=True)
    _, h_mid = core_ssm.selective_scan(
        u[:, :11], dt[:, :11], A, Bm[:, :11], Cm[:, :11], Dk, pos[:, :11],
        method="sequential", return_state=True)
    y_rest, h_end = core_ssm.selective_scan(
        u[:, 11:], dt[:, 11:], A, Bm[:, 11:], Cm[:, 11:], Dk, pos[:, 11:],
        h0=h_mid, method="blocked", chunk=4, return_state=True)
    np.testing.assert_allclose(np.asarray(y_rest), np.asarray(y_all[:, 11:]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_end), np.asarray(h_all),
                               atol=1e-4, rtol=1e-4)


def test_blocked_grad_does_not_cross_boundary(rng):
    """Backward PUI on the blocked path (paper §3.4)."""
    Bz, L, Dm, N = 1, 16, 4, 3
    u, dt, A, Bm, Cm, Dk, _ = _ssm_inputs(rng, Bz, L, Dm, N)
    pos = jnp.concatenate([jnp.arange(8), jnp.arange(8)])[None]

    def loss(u_in):
        y = core_ssm.selective_scan(u_in, dt, A, Bm, Cm, Dk, pos,
                                    method="blocked", chunk=8)
        return (y[:, 8:] ** 2).sum()

    g = jax.grad(loss)(u)
    np.testing.assert_allclose(g[:, :8], 0.0, atol=1e-7)
    assert float(jnp.abs(g[:, 8:]).max()) > 0


def test_blocked_jaxpr_has_no_full_trajectory():
    """The structural memory claim: `blocked` never builds a (B, L, D, N)
    intermediate; `chunked` does (it materializes decay + input tensors)."""
    Bz, L, Dm, N = 1, 512, 32, 8
    args = (jnp.zeros((Bz, L, Dm)), jnp.full((Bz, L, Dm), 0.1),
            -jnp.ones((Dm, N)), jnp.zeros((Bz, L, N)),
            jnp.zeros((Bz, L, N)), jnp.zeros((Dm,)),
            jnp.zeros((Bz, L), jnp.int32))

    def has_full(method, **kw):
        jaxpr = jax.make_jaxpr(lambda *a: core_ssm.selective_scan(
            *a, method=method, chunk=64, **kw))(*args)
        want = (Bz, L, Dm, N)
        return any(getattr(v.aval, "shape", None) == want
                   for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars)

    assert has_full("chunked")
    assert not has_full("blocked", intra="assoc")
    assert not has_full("blocked", intra="matmul")


# ---------------------------------------------------------------------------
# Pallas scan kernel (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Bz,L,Dm,N,block_d,chunk", [
    (2, 24, 10, 4, 8, 8),
    (1, 33, 8, 16, 8, 8),
    # three channel blocks, the last chunk padded (40 = 16 + 16 + 8)
    (2, 40, 24, 4, 8, 16),
    (1, 100, 16, 16, 128, 32),
])
def test_pallas_scan_fwd(rng, Bz, L, Dm, N, block_d, chunk):
    u, dt, A, Bm, Cm, Dk, pos = _ssm_inputs(rng, Bz, L, Dm, N)
    y_ref = selective_scan_ref(u, dt, A, Bm, Cm, Dk, pos)
    y = kops_scan(u, dt, A, Bm, Cm, Dk, pos, backend="pallas",
                  block_d=block_d, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)


def _pallas_grads_match_ref(args, pos, of_max=None, **kw):
    """Gradients of Σ y² through the Pallas custom_vjp against kernels/ref.py
    (the naive f32 recurrence on the same operands, upcast), for u, Δ, A, B,
    C, D: elementwise at f32, or with ``of_max`` each leaf's largest error
    within that share of the reference leaf's largest magnitude."""
    def lp(*a):
        return (kops_scan(*a, pos, backend="pallas", **kw)
                .astype(jnp.float32) ** 2).sum()

    def lr(*a):
        a = [x.astype(jnp.float32) for x in a]
        return (selective_scan_ref(*a, pos) ** 2).sum()

    gp = jax.grad(lp, argnums=tuple(range(6)))(*args)
    gr = jax.grad(lr, argnums=tuple(range(6)))(*args)
    for name, a, b in zip("u dt A B C D".split(), gp, gr):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        if of_max is None:
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3,
                                       err_msg=f"grad {name}")
        else:
            err, top = np.abs(a - b).max(), np.abs(b).max()
            assert err <= of_max * top, (name, err, top)


@pytest.mark.parametrize("Bz,L,Dm,N,dtype", [
    pytest.param(2, 24, 10, 4, "float32", id="step"),
    # under one chunk, and not a whole number of 8-row sublane groups
    pytest.param(3, 17, 5, 3, "float32", id="step-short"),
    # bf16 activations, as the train cells run the kernel (A, D stay f32)
    pytest.param(2, 24, 10, 4, "bfloat16", id="step-bf16"),
])
def test_pallas_blocked_grads(rng, Bz, L, Dm, N, dtype):
    u, dt, A, Bm, Cm, Dk, pos = _ssm_inputs(rng, Bz, L, Dm, N)
    if dtype == "float32":
        _pallas_grads_match_ref((u, dt, A, Bm, Cm, Dk), pos,
                                block_d=8, chunk=8)
        return
    bf = lambda x: x.astype(jnp.bfloat16)
    # the reference runs on the same bf16-rounded operands in f32; the
    # kernel returns y and the gradients of u, Δ, B, C in bf16 (8-bit
    # mantissa), so a leaf may be off by a few of its largest value's ulps
    _pallas_grads_match_ref((bf(u), bf(dt), A, bf(Bm), bf(Cm), Dk), pos,
                            of_max=2e-2, block_d=8, chunk=8)


@pytest.mark.parametrize("L,Dm,N,block_d,chunk,starts", [
    pytest.param(64, 8, 4, 8, 16, ([0, 5, 48], [0, 16, 40]), id="step"),
    # the chip's tile: block_d 128, N 16, chunk 256 (the backward's row
    # groups of 32); a reset on the second chunk's first row and one inside
    # a row group
    pytest.param(512, 128, 16, 128, 256, ([0, 104, 256],),
                 id="step-chip-tile"),
    # chunk 128 over two chunks: the backward's row groups of 16
    # (_bwd_group(128, 2)); resets on the second chunk's first row and
    # inside a group
    pytest.param(256, 128, 16, 128, 128, ([0, 40, 128],),
                 id="step-chunk128"),
])
def test_pallas_grads_document_across_chunks(rng, L, Dm, N, block_d, chunk,
                                             starts):
    """Documents cross chunk boundaries, and resets fall on a chunk's first
    row and inside it: the backward's adjoint carry must pass the first and
    stop at the others. In the small case documents cross the boundaries
    at 16 and 32, and resets fall on the first row of the chunks at 16
    (row 1) and 48 (row 0)."""
    Bz = len(starts)
    u, dt, A, Bm, Cm, Dk, _ = _ssm_inputs(rng, Bz, L, Dm, N)
    pos = np.zeros((Bz, L), np.int32)
    for b, cuts in enumerate(starts):
        for s0, s1 in zip(cuts, cuts[1:] + [L]):
            pos[b, s0:s1] = np.arange(s1 - s0)
    _pallas_grads_match_ref((u, dt, A, Bm, Cm, Dk), jnp.asarray(pos),
                            block_d=block_d, chunk=chunk)


@pytest.mark.parametrize("L,Dm,cut,block_d,chunk", [
    pytest.param(16, 8, 8, 8, 8, id="step"),
    # the reset on the first row of the second chunk, channels over two
    # blocks of 8
    pytest.param(32, 16, 16, 8, 16, id="step-two-blocks"),
])
def test_pallas_blocked_reset_blocks_grad(rng, L, Dm, cut, block_d, chunk):
    u, dt, A, Bm, Cm, Dk, _ = _ssm_inputs(rng, 1, L, Dm, 4)
    pos = jnp.concatenate([jnp.arange(cut), jnp.arange(L - cut)])[None]

    def loss(u_in):
        y = kops_scan(u_in, dt, A, Bm, Cm, Dk, pos, backend="pallas",
                      block_d=block_d, chunk=chunk)
        return (y[:, cut:] ** 2).sum()

    g = jax.grad(loss)(u)
    np.testing.assert_allclose(g[:, :cut], 0.0, atol=1e-7)
    assert float(jnp.abs(g[:, cut:]).max()) > 0


_DOCS = (0, 11, 40, 41, 70)          # document starts in one row of 96


def _docs_alone(f, L, u, dt, Bm, Cm):
    """``f(u, dt, Bm, Cm, positions)`` with each document of the packed row
    alone in a row of its own, from a zero state (positions 0.., zeros
    after it), and the outputs gathered back into the packed row."""
    starts = np.asarray(_DOCS)
    lens = np.diff(np.append(starts, L))
    t = np.arange(lens.max())
    take = np.minimum(starts[:, None] + t, L - 1)         # (docs, T)
    keep = jnp.asarray(t < lens[:, None])[..., None]
    rows = [jnp.where(keep, x[0][take], 0) for x in (u, dt, Bm, Cm)]
    pos = jnp.broadcast_to(jnp.arange(len(t), dtype=jnp.int32), take.shape)
    y = f(*rows, pos)
    doc = np.searchsorted(starts, np.arange(L), side="right") - 1
    return y[doc, np.arange(L) - starts[doc]][None]


@pytest.mark.parametrize("direction", ["fwd", "grad"])
def test_pallas_packed_row_equals_documents_alone(rng, direction):
    """PackMamba's property on the kernel the train cells run: a packed row
    through ``ops.selective_scan(backend="pallas")`` gives each document
    what it gets scanned alone from a zero state (a one-token document
    included), outputs and input gradients alike."""
    L = 96
    u, dt, A, Bm, Cm, Dk, _ = _ssm_inputs(rng, 1, L, 16, 4)
    pos = np.zeros((1, L), np.int32)
    for s0, s1 in zip(_DOCS, list(_DOCS[1:]) + [L]):
        pos[0, s0:s1] = np.arange(s1 - s0)
    pos = jnp.asarray(pos)
    kw = dict(backend="pallas", block_d=8, chunk=32)
    w = jnp.asarray(rng.normal(size=(1, L, 16)), jnp.float32)

    def packed(u, dt, Bm, Cm):
        return kops_scan(u, dt, A, Bm, Cm, Dk, pos, **kw)

    def alone(u, dt, Bm, Cm):
        return _docs_alone(lambda u, dt, Bm, Cm, p: kops_scan(
            u, dt, A, Bm, Cm, Dk, p, **kw), L, u, dt, Bm, Cm)

    args = (u, dt, Bm, Cm)
    if direction == "fwd":
        np.testing.assert_allclose(packed(*args), alone(*args), atol=1e-5,
                                   rtol=1e-5)
        return
    grad = lambda f: jax.grad(lambda *a: (f(*a) * w).sum(),
                              argnums=(0, 1, 2, 3))
    gp, ga = grad(packed)(*args), grad(alone)(*args)
    for name, a, b in zip(("u", "dt", "B", "C"), gp, ga):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4,
                                   err_msg=f"grad {name}")


@pytest.mark.parametrize("T,nL,G", [
    (256, 16, 32),       # the train cells: chunk 256 over 4096 rows
    (256, 1, 32),
    (128, 4, 16),
    (64, 2, 8),
    (24, 1, 8),
    (16, 2, 2),
])
def test_bwd_group(T, nL, G):
    """The backward's row group: the most rows, up to 32, that divide the
    chunk and leave the lane-dense dB, dC block whole 8-row tiles (T/G a
    multiple of 8) unless the chunk is the whole sequence."""
    from repro.kernels.selective_scan import _bwd_group
    got = _bwd_group(T, nL)
    assert got == G
    assert T % got == 0
    assert nL == 1 or (T // got) % 8 == 0
