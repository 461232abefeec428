"""Flash-attention kernel: shape/dtype sweeps against the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention


def _mk(B, Sq, Skv, H, KVH, D, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, KVH, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, KVH, D), dtype)
    return q, k, v


SHAPES = [
    (1, 16, 16, 4, 4, 16),      # MHA tiny
    (2, 67, 67, 8, 2, 32),      # GQA, ragged seq
    (2, 128, 128, 4, 1, 64),    # kv=1 (gemma-style)
    (1, 33, 129, 4, 2, 24),     # cross-length, odd dims
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 16), (False, 0)])
def test_matches_oracle(shape, impl, causal, window):
    B, Sq, Skv, H, KVH, D = shape
    q, k, v = _mk(B, Sq, Skv, H, KVH, D)
    want = flash_attention(q, k, v, causal=causal, window=window, impl="naive")
    got = flash_attention(q, k, v, causal=causal, window=window, impl=impl,
                          chunk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_dtypes(dtype, impl):
    q, k, v = _mk(2, 40, 40, 4, 2, 32, dtype=dtype)
    want = flash_attention(q, k, v, impl="naive")
    got = flash_attention(q, k, v, impl=impl, chunk=16)
    assert got.dtype == dtype
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_decode_offset():
    q, k, v = _mk(2, 1, 64, 8, 4, 32)
    want = flash_attention(q, k, v, causal=True, q_offset=63, impl="naive")
    for impl in ("reference", "pallas"):
        got = flash_attention(q, k, v, causal=True, q_offset=63, impl=impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_gradients_match():
    q, k, v = _mk(1, 24, 24, 4, 2, 16)

    def loss(impl):
        return lambda q, k, v: (
            flash_attention(q, k, v, impl=impl, chunk=8) ** 2).sum()

    g_ref = jax.grad(loss("naive"), argnums=(0, 1, 2))(q, k, v)
    for impl in ("reference", "pallas"):
        g = jax.grad(loss(impl), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("shape,causal,window", [
    ((2, 67, 67, 8, 2, 32), True, 0),     # GQA, ragged, multi-block
    ((1, 40, 40, 4, 1, 16), True, 16),    # kv=1, sliding window
    ((2, 33, 64, 4, 4, 24), False, 0),    # cross-length, non-causal
    ((1, 128, 128, 8, 2, 64), True, 0),   # multiple q AND kv blocks
])
def test_pallas_flash_backward_kernels(shape, causal, window):
    """The true Pallas backward (dQ pass + dK/dV pass with grid-carried
    accumulators and the forward's LSE) vs the oracle's autodiff."""
    B, Sq, Skv, H, KVH, D = shape
    q, k, v = _mk(B, Sq, Skv, H, KVH, D)

    def loss(impl):
        return lambda q, k, v: (flash_attention(
            q, k, v, causal=causal, window=window, impl=impl,
            chunk=16) ** 2).sum()

    g_ref = jax.grad(loss("naive"), argnums=(0, 1, 2))(q, k, v)
    g_pls = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_pls):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-4, rtol=2e-4)


def test_forward_lse_is_correct():
    from repro.kernels.flash_attention.kernel import flash_attention_pallas_fwd
    q, k, v = _mk(2, 32, 32, 4, 2, 16)
    out, lse = flash_attention_pallas_fwd(q, k, v, causal=True)
    # independent lse: logsumexp of masked scaled scores
    G = 2
    qf = (np.asarray(q, np.float32) * 16 ** -0.5).reshape(2, 32, 2, 2, 16)
    s = np.einsum("bqhgd,bkhd->bqhgk", qf, np.asarray(k, np.float32))
    mask = np.tril(np.ones((32, 32), bool))
    s = np.where(mask[None, :, None, None, :], s, -1e30)
    want = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) \
        + s.max(-1)
    want = want.reshape(2, 32, 4)
    np.testing.assert_allclose(np.asarray(lse), want, atol=1e-4, rtol=1e-4)


def test_fully_masked_rows_are_zero():
    # window smaller than gap: early queries see nothing but themselves;
    # fully-masked kv blocks must not poison the output with NaNs.
    q, k, v = _mk(1, 32, 32, 2, 2, 16)
    out = flash_attention(q, k, v, causal=True, window=4, impl="pallas")
    assert bool(jnp.isfinite(out).all())


# ------------------------------------------------------------ tile schedule
#
# Pinned small tiles put dead (wholly masked), partial and full (q tile,
# k tile) pairs into one grid; each case holds the Mosaic kernels
# (interpret mode) to the oracle: output and lse forward, dq/dk/dv back.

SCHEDULES = {
    # id: (B, Sq, Skv, H, KVH, D), causal, window, q_offset, (bq, bk)
    "causal": ((1, 64, 64, 2, 2, 16), True, 0, 0, (16, 16)),
    "non_causal": ((1, 48, 64, 2, 2, 16), False, 0, 0, (16, 16)),
    "window_in_tile": ((1, 64, 64, 2, 1, 16), True, 5, 0, (16, 16)),
    "window_over_tiles": ((1, 96, 96, 2, 1, 16), True, 40, 0, (16, 16)),
    "q_offset": ((2, 24, 64, 2, 1, 16), True, 0, 40, (8, 16)),
    "ragged_300": ((1, 300, 300, 2, 1, 16), True, 0, 0, (128, 128)),
    "bq_lt_bk": ((1, 64, 64, 2, 2, 16), True, 0, 0, (16, 32)),
    "bq_gt_bk": ((1, 64, 64, 2, 2, 16), True, 24, 0, (32, 16)),
    "gqa_g2": ((2, 48, 48, 4, 2, 16), True, 0, 0, (16, 16)),
}


def _lse_oracle(q, k, causal, window, q_offset):
    B, Sq, H, D = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    qf = (np.asarray(q, np.float64) * D ** -0.5).reshape(B, Sq, KVH, G, D)
    s = np.einsum("bqhgd,bkhd->bqhgk", qf, np.asarray(k, np.float64))
    qpos = np.arange(Sq)[:, None] + q_offset
    kpos = np.arange(Skv)[None, :]
    mask = np.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = np.where(mask[None, :, None, None, :], s, -np.inf)
    m = s.max(-1)
    return (np.log(np.exp(s - m[..., None]).sum(-1)) + m).reshape(B, Sq, H)


@pytest.mark.parametrize("case", list(SCHEDULES))
def test_tile_schedule_forward_matches_oracle(case):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas_fwd
    shape, causal, window, q_offset, (bq, bk) = SCHEDULES[case]
    q, k, v = _mk(*shape)
    want = flash_attention(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, impl="naive")
    out, lse = flash_attention_pallas_fwd(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse),
                               _lse_oracle(q, k, causal, window, q_offset),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", list(SCHEDULES))
def test_tile_schedule_backward_matches_oracle(case):
    shape, causal, window, q_offset, (bq, bk) = SCHEDULES[case]
    q, k, v = _mk(*shape)

    def loss(impl, design=None):
        return lambda q, k, v: (flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            impl=impl, design=design) ** 2).sum()

    g_ref = jax.grad(loss("naive"), argnums=(0, 1, 2))(q, k, v)
    g_pls = jax.grad(loss("pallas", (bq, bk, 4, 2)),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_pls):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("case", list(SCHEDULES))
def test_tile_classes_match_the_mask(case):
    """Every pair's class against its explicit mask: live iff some position
    is unmasked, full iff none is; the live span of each row and column
    (what a dead pair's index map repeats) holds every live pair."""
    from repro.kernels.flash_attention.kernel import _Tiles
    (_, Sq, Skv, _, _, _), causal, window, q_offset, (bq, bk) = \
        SCHEDULES[case]
    t = _Tiles(bq, bk, q_offset, Sq, Skv, causal, window)
    rows = np.arange(t.nq * bq)[:, None]
    cols = np.arange(t.nk * bk)[None, :]
    mask = (rows < Sq) & (cols < Skv)
    if causal:
        mask &= cols <= rows + q_offset
    if window > 0:
        mask &= cols > rows + q_offset - window
    classes = set()
    for qi in range(t.nq):
        k_first, k_last = t.k_span(qi)
        for ki in range(t.nk):
            m = mask[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            live, full = t.pair(qi, ki)
            assert (bool(live), bool(full)) == (m.any(), m.all()), (qi, ki)
            q_first, q_last = t.q_span(ki)
            if live:
                assert k_first <= ki <= k_last and q_first <= qi <= q_last
            classes.add("full" if full else "partial" if live else "dead")
    if causal:
        assert {"dead", "partial"} <= classes
    if window == 0 or window > 2 * max(bq, bk):
        assert "full" in classes


@pytest.mark.parametrize("S,block,causal,want", [
    (4096, 512, True, (64, 36)),
    (4096, 128, True, (1024, 528)),
    (4096, 512, False, (64, 64)),
])
def test_tile_counter(S, block, causal, want):
    """The live share each Mosaic call adds to ``repro.obs`` when traced."""
    from repro import obs
    from repro.kernels.flash_attention.kernel import tile_schedule
    assert tile_schedule(S, S, causal=causal, block_q=block,
                         block_k=block) == want
    q = jax.ShapeDtypeStruct((1, S, 1, 16), jnp.float32)
    before = (obs.counter("flash_attention.tiles"),
              obs.counter("flash_attention.tiles_live"))
    jax.eval_shape(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, impl="pallas",
        design=(block, block, 4, 2)), q, q, q)
    assert (obs.counter("flash_attention.tiles") - before[0],
            obs.counter("flash_attention.tiles_live") - before[1]) == want


def test_tpu_tile_resolution():
    """On the TPU a call with no cache entry and no pinned design takes the
    measured tile, cut to divide its length rounded up to 128; a cache
    entry or a pinned design is taken as given."""
    from repro.kernels import dispatch, tuning
    from repro.kernels.flash_attention.ops import _mosaic_blocks
    bq, bk = tuning.TPU_FLASH_TILES
    assert tuning.flash_tile(4096, 1024) == 1024
    assert tuning.flash_tile(4096, 512) == 512
    assert tuning.flash_tile(1000, 512) == 512
    assert tuning.flash_tile(300, 512) == 128
    assert tuning.flash_tile(640, 512) == 128
    assert tuning.flash_tile(1, 512) == 128

    def blocks(sq, skv, design=None, backend="tpu", head_dim=64):
        d = dispatch.resolve("pallas", backend=backend,
                             kernel="flash_attention", shape=(skv, head_dim),
                             design=design)
        return _mosaic_blocks(d, design is not None, sq, skv, head_dim)

    assert blocks(8192, 8192) == (bq, bk)          # no skv8192_d64 entry
    assert blocks(8192, 8192, head_dim=192) == (bq, bk)
    cap = tuning.TPU_FLASH_TILE_AREA // 512
    assert blocks(8192, 8192, head_dim=512) == (min(bq, cap), min(bk, cap))
    assert blocks(300, 300) == (128, 128)
    assert blocks(8192, 8192, design=(128, 256, 4, 2)) == (128, 256)
    assert blocks(8192, 8192, design=(0, 0, 4, 2)) == (128, 128)
    # off the TPU the kernel default stays
    assert blocks(8192, 8192, backend="cpu") == (128, 128)
