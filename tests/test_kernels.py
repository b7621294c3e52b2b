"""Pallas kernels vs pure-jnp oracle: shape/dtype sweeps, fwd + grads.

On the CPU the kernels run under the Pallas interpreter (the mode follows
the platform, kernels/__init__.py); tests/test_tpu_compile.py compiles the
same entry points for a described TPU v5e."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ops import selective_scan, conv1d_pack
from repro.kernels.ref import selective_scan_ref, conv1d_pack_ref


def _scan_inputs(rng, Bz, L, Dm, N, dtype):
    u = jnp.asarray(rng.normal(size=(Bz, L, Dm)), dtype)
    dt = jnp.asarray(rng.uniform(0.05, 0.5, (Bz, L, Dm)), dtype)
    A = -jnp.exp(jnp.asarray(rng.normal(size=(Dm, N)), jnp.float32))
    Bm = jnp.asarray(rng.normal(size=(Bz, L, N)), dtype)
    Cm = jnp.asarray(rng.normal(size=(Bz, L, N)), dtype)
    Dk = jnp.asarray(rng.normal(size=(Dm,)), jnp.float32)
    # packed positions: a few segments per row
    pos = np.zeros((Bz, L), np.int32)
    for b in range(Bz):
        cuts = sorted(rng.choice(np.arange(1, L), size=min(3, L - 1),
                                 replace=False)) if L > 2 else []
        prev = 0
        for c in list(cuts) + [L]:
            pos[b, prev:c] = np.arange(c - prev)
            prev = c
    return u, dt, A, Bm, Cm, Dk, jnp.asarray(pos)


SCAN_SHAPES = [(1, 8, 4, 2), (2, 24, 10, 4), (1, 64, 16, 16), (3, 17, 5, 3)]


@pytest.mark.parametrize("Bz,L,Dm,N", SCAN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_fwd(Bz, L, Dm, N, dtype):
    rng = np.random.default_rng(Bz * 100 + L)
    u, dt, A, Bm, Cm, Dk, pos = _scan_inputs(rng, Bz, L, Dm, N, dtype)
    y_ref = selective_scan_ref(u, dt, A, Bm, Cm, Dk, pos)
    y_pal = selective_scan(u, dt, A, Bm, Cm, Dk, pos, backend="pallas",
                           block_d=8, chunk=8)
    y_xla = selective_scan(u, dt, A, Bm, Cm, Dk, pos, backend="xla",
                           xla_chunk=8)
    atol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_ref, np.float32), atol=atol)
    np.testing.assert_allclose(np.asarray(y_xla, np.float32),
                               np.asarray(y_ref, np.float32), atol=atol)


@pytest.mark.parametrize("Bz,L,Dm,N", [(2, 24, 10, 4), (1, 16, 8, 16)])
def test_selective_scan_grads(Bz, L, Dm, N):
    rng = np.random.default_rng(5)
    u, dt, A, Bm, Cm, Dk, pos = _scan_inputs(rng, Bz, L, Dm, N, jnp.float32)

    def lp(*args):
        return (selective_scan(*args, pos, backend="pallas",
                               block_d=8, chunk=8) ** 2).sum()

    def lr(*args):
        return (selective_scan_ref(*args, pos) ** 2).sum()

    gp = jax.grad(lp, argnums=tuple(range(6)))(u, dt, A, Bm, Cm, Dk)
    gr = jax.grad(lr, argnums=tuple(range(6)))(u, dt, A, Bm, Cm, Dk)
    for name, a, b in zip("u dt A B C D".split(), gp, gr):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3,
                                   err_msg=f"grad {name}")


def test_selective_scan_reset_blocks_grad():
    """The paper's backward claim on the actual kernel: no gradient crosses
    a packed-sequence boundary."""
    rng = np.random.default_rng(6)
    u, dt, A, Bm, Cm, Dk, _ = _scan_inputs(rng, 1, 16, 8, 4, jnp.float32)
    pos = jnp.concatenate([jnp.arange(8), jnp.arange(8)])[None]

    def loss(u_in):
        y = selective_scan(u_in, dt, A, Bm, Cm, Dk, pos, backend="pallas",
                           block_d=8, chunk=8)
        return (y[:, 8:] ** 2).sum()

    g = jax.grad(loss)(u)
    np.testing.assert_allclose(g[:, :8], 0.0, atol=1e-7)
    assert float(jnp.abs(g[:, 8:]).max()) > 0


CONV_SHAPES = [(1, 8, 4, 2), (2, 24, 10, 4), (1, 64, 16, 4), (3, 17, 5, 3)]


@pytest.mark.parametrize("Bz,L,Dm,W", CONV_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv1d_pack_fwd(Bz, L, Dm, W, dtype):
    rng = np.random.default_rng(Bz * 31 + L)
    x = jnp.asarray(rng.normal(size=(Bz, L, Dm)), dtype)
    w = jnp.asarray(rng.normal(size=(W, Dm)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(Dm,)), jnp.float32)
    pos = jnp.asarray(np.tile(
        np.concatenate([np.arange(L // 2), np.arange(L - L // 2)]),
        (Bz, 1)).astype(np.int32))
    y_ref = conv1d_pack_ref(x, w, b, pos)
    y_pal = conv1d_pack(x, w, b, pos, backend="pallas", block_d=8, chunk=8)
    y_xla = conv1d_pack(x, w, b, pos, backend="xla")
    atol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_ref, np.float32), atol=atol)
    np.testing.assert_allclose(np.asarray(y_xla, np.float32),
                               np.asarray(y_ref, np.float32), atol=atol)


def test_conv1d_pack_grads():
    rng = np.random.default_rng(8)
    Bz, L, Dm, W = 2, 24, 10, 4
    x = jnp.asarray(rng.normal(size=(Bz, L, Dm)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(W, Dm)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(Dm,)), jnp.float32)
    pos = jnp.asarray(np.tile(np.concatenate([np.arange(9), np.arange(15)]),
                              (Bz, 1)).astype(np.int32))

    def lp(x, w, b):
        return (conv1d_pack(x, w, b, pos, backend="pallas",
                            block_d=8, chunk=8) ** 2).sum()

    def lr(x, w, b):
        return (conv1d_pack_ref(x, w, b, pos) ** 2).sum()

    gp = jax.grad(lp, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(lr, argnums=(0, 1, 2))(x, w, b)
    for name, a, bb in zip("x w b".split(), gp, gr):
        np.testing.assert_allclose(a, bb, atol=1e-4, rtol=1e-3,
                                   err_msg=f"grad {name}")


def test_kernels_under_jit_and_vmapless_batching():
    rng = np.random.default_rng(9)
    u, dt, A, Bm, Cm, Dk, pos = _scan_inputs(rng, 2, 16, 8, 4, jnp.float32)
    f = jax.jit(lambda *a: selective_scan(*a, backend="pallas",
                                          block_d=8, chunk=8))
    y1 = f(u, dt, A, Bm, Cm, Dk, pos)
    y2 = selective_scan(u, dt, A, Bm, Cm, Dk, pos, backend="pallas",
                        block_d=8, chunk=8)
    np.testing.assert_allclose(y1, y2, atol=1e-6)
