"""SSD (Mamba-2) kernel: sweeps vs the sequential-recurrence oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd.ops import ssd_decode, ssd_scan


def _mk(B, S, H, P, G, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    D = jax.random.normal(ks[5], (H,))
    return x, dt, A, Bm, Cm, D


SHAPES = [
    (1, 32, 2, 8, 1, 4),
    (2, 96, 4, 16, 2, 8),      # grouped B/C
    (2, 83, 4, 16, 1, 8),      # ragged (chunk padding path)
    (1, 64, 8, 32, 4, 16),
]

# many heads share one group's B, C and C·Bᵀ in the Mosaic kernels; the
# last has one head per group
SHARED = [
    (1, 64, 16, 8, 1, 4),
    (2, 64, 8, 16, 2, 8),
    (1, 64, 4, 8, 4, 4),
]


@pytest.mark.parametrize("shape", SHAPES + SHARED)
@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_matches_oracle(shape, impl):
    x, dt, A, Bm, Cm, D = _mk(*shape)
    y0, s0 = ssd_scan(x, dt, A, Bm, Cm, D, impl="naive")
    y1, s1 = ssd_scan(x, dt, A, Bm, Cm, D, impl=impl, chunk=32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("chunk", [8, 16, 64, 128])
def test_chunk_size_invariance(chunk):
    x, dt, A, Bm, Cm, D = _mk(2, 64, 2, 8, 1, 4)
    y0, s0 = ssd_scan(x, dt, A, Bm, Cm, D, impl="naive")
    y, s = ssd_scan(x, dt, A, Bm, Cm, D, impl="reference", chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), atol=1e-4,
                               rtol=1e-4)


def test_init_state_chaining():
    """Running two halves with state carry == one full scan (the decode/
    chunked-prefill contract)."""
    x, dt, A, Bm, Cm, D = _mk(2, 64, 4, 16, 2, 8)
    y_full, s_full = ssd_scan(x, dt, A, Bm, Cm, D, impl="naive")
    yA, sA = ssd_scan(x[:, :32], dt[:, :32], A, Bm[:, :32], Cm[:, :32], D,
                      impl="reference", chunk=16)
    yB, sB = ssd_scan(x[:, 32:], dt[:, 32:], A, Bm[:, 32:], Cm[:, 32:], D,
                      impl="reference", chunk=16, init_state=sA)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([yA, yB], 1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sB), np.asarray(s_full), atol=1e-4,
                               rtol=1e-4)


def test_decode_step_matches_scan():
    x, dt, A, Bm, Cm, D = _mk(2, 17, 4, 8, 2, 4)
    y_full, s_full = ssd_scan(x, dt, A, Bm, Cm, D, impl="naive")
    state = jnp.zeros((2, 4, 8, 4))
    ys = []
    for t in range(17):
        y, state = ssd_decode(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D,
                              state)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s_full),
                               atol=1e-4, rtol=1e-4)


def test_gradients():
    x, dt, A, Bm, Cm, D = _mk(1, 48, 2, 8, 1, 4)

    def loss(impl):
        return lambda x, dt: (
            ssd_scan(x, dt, A, Bm, Cm, D, impl=impl, chunk=16)[0] ** 2).mean()

    g0 = jax.grad(loss("naive"), argnums=(0, 1))(x, dt)
    for impl in ("reference", "pallas"):
        g = jax.grad(loss(impl), argnums=(0, 1))(x, dt)
        for a, b in zip(g0, g):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-4, rtol=1e-3)
            assert np.isfinite(np.asarray(b)).all()


def test_pallas_backward_kernel_all_operands():
    """The true Pallas intra-chunk backward (dx/ddt/dA/dB/dC through the
    decay-matrix chain rule) vs oracle autodiff, including grouped B/C,
    ragged padding, and final-state cotangents."""
    x, dt, A, Bm, Cm, D = _mk(2, 83, 4, 16, 2, 8)   # ragged, grouped

    def loss(impl):
        def f(x, dt, Bm, Cm, D):
            y, s = ssd_scan(x, dt, A, Bm, Cm, D, impl=impl, chunk=32)
            return (y ** 2).mean() + (s ** 2).mean()
        return f

    g0 = jax.grad(loss("naive"), argnums=(0, 1, 2, 3, 4))(x, dt, Bm, Cm, D)
    g1 = jax.grad(loss("pallas"), argnums=(0, 1, 2, 3, 4))(x, dt, Bm, Cm, D)
    for a, b in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-4,
                                   rtol=1e-3)


@pytest.mark.parametrize("shape", SHARED)
def test_pallas_gradients_with_heads_sharing_a_group(shape):
    """dx, ddt, dA, dB and dC against oracle autodiff where the kernels sum
    dB / dC over many heads of a group (or over one, G == H)."""
    x, dt, A, Bm, Cm, D = _mk(*shape)

    def loss(impl):
        def f(x, dt, A, Bm, Cm):
            y, s = ssd_scan(x, dt, A, Bm, Cm, D, impl=impl, chunk=32)
            return (y ** 2).mean() + (s ** 2).mean()
        return f

    args = (x, dt, A, Bm, Cm)
    g0 = jax.grad(loss("naive"), argnums=(0, 1, 2, 3, 4))(*args)
    g1 = jax.grad(loss("pallas"), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-4,
                                   rtol=1e-3)


def _check_intra_backward(B, S, H, P, G, N, chunk, seed):
    from repro.kernels.ssd.kernel import ssd_chunk_pallas_bwd
    from repro.kernels.ssd.ops import _intra_chunk_jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    dy = jax.random.normal(ks[5], (B, S, H, P))
    dstates = jax.random.normal(ks[6], (B, S // chunk, H, P, N))
    dcum = jax.random.normal(ks[7], (B, S, H))
    _, vjp = jax.vjp(lambda *a: _intra_chunk_jnp(*a, chunk), x, dt, A, Bm,
                     Cm)
    want = vjp((dy, dstates, dcum))
    got = ssd_chunk_pallas_bwd(x, dt, A, Bm, Cm, dy, dstates, dcum,
                               chunk=chunk)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4,
                                   rtol=1e-4)


def test_pallas_intra_backward_matches_vjp():
    _check_intra_backward(1, 64, 2, 8, 1, 4, chunk=32, seed=3)


@pytest.mark.parametrize("shape", SHARED)
def test_pallas_intra_backward_heads_sharing_a_group(shape):
    """(dx, ddt, dA, dB, dC) of the intra-chunk kernel vs its jnp twin's
    VJP, with dB / dC gathered over the group's heads inside the kernel."""
    _check_intra_backward(*shape, chunk=32, seed=5)


def test_counters_heads_and_groups():
    """A traced Mosaic call adds B·H to ``ssd.heads`` and B·G to
    ``ssd.groups``: how many heads share one C·Bᵀ."""
    from repro import obs
    x, dt, A, Bm, Cm, D = (jax.ShapeDtypeStruct(a.shape, a.dtype)
                           for a in _mk(2, 64, 8, 16, 2, 8))
    before = obs.counter("ssd.heads"), obs.counter("ssd.groups")
    jax.eval_shape(lambda *a: ssd_scan(*a, impl="pallas", chunk=32),
                   x, dt, A, Bm, Cm, D)
    assert (obs.counter("ssd.heads") - before[0],
            obs.counter("ssd.groups") - before[1]) == (16, 4)
    jax.eval_shape(lambda *a: ssd_scan(*a, impl="reference", chunk=32),
                   x, dt, A, Bm, Cm, D)
    assert obs.counter("ssd.heads") - before[0] == 16
