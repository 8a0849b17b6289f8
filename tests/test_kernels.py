"""Per-kernel allclose tests vs the pure-jnp oracles (ref.py), sweeping
shapes and dtypes — deliverable (c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def key(i):
    return jax.random.PRNGKey(i)


# ---------------------------------------------------------------------------
# gather_blocks (FlashH2D analogue)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nb,bs,d", [(8, 32, 64), (64, 32, 128), (17, 16, 96),
                                     (128, 8, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_blocks(nb, bs, d, dtype):
    pool = jax.random.normal(key(0), (nb, bs, d), jnp.float32).astype(dtype)
    idx = jax.random.randint(key(1), (min(nb, 16),), 0, nb)
    out = ops.gather_blocks(pool, idx)
    want = ref.gather_blocks(pool, idx)
    assert out.shape == want.shape and out.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_gather_blocks_duplicate_and_boundary_indices():
    pool = jax.random.normal(key(2), (16, 32, 64), jnp.float32)
    idx = jnp.array([0, 0, 15, 15, 7], jnp.int32)
    np.testing.assert_array_equal(np.asarray(ops.gather_blocks(pool, idx)),
                                  np.asarray(ref.gather_blocks(pool, idx)))


# ---------------------------------------------------------------------------
# scatter_blocks (FlashD2H analogue)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nb,bs,d,n_new", [(16, 32, 64, 4), (64, 16, 128, 8),
                                           (9, 8, 32, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scatter_blocks(nb, bs, d, n_new, dtype):
    pool = jax.random.normal(key(3), (nb, bs, d), jnp.float32).astype(dtype)
    new = jax.random.normal(key(4), (n_new * bs, d), jnp.float32).astype(dtype)
    dest = jax.random.choice(key(5), nb, (n_new,), replace=False)
    out = ops.scatter_blocks(pool, new, dest)
    want = ref.scatter_blocks(pool, new, dest)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_scatter_then_gather_roundtrip():
    pool = jnp.zeros((32, 16, 64))
    new = jax.random.normal(key(6), (4 * 16, 64), jnp.float32)
    dest = jnp.array([3, 9, 20, 31])
    pool2 = ops.scatter_blocks(pool, new, dest)
    got = ops.gather_blocks(pool2, dest)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 64),
                               np.asarray(new), rtol=1e-6)


# ---------------------------------------------------------------------------
# head-major (H, NB, bs, D) variants — persistent device plane row slots
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,nb,bs,d,k", [(1, 8, 16, 32, 3), (2, 24, 8, 64, 6),
                                         (4, 17, 16, 32, 5)])
def test_gather_blocks_hkv(h, nb, bs, d, k):
    pool = jax.random.normal(key(20), (h, nb, bs, d), jnp.float32)
    idx = jax.random.randint(key(21), (k,), 0, nb)
    out = ops.gather_blocks_hkv(pool, idx)
    want = ref.gather_blocks_hkv(pool, idx)
    assert out.shape == (h, k, bs, d)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("h,nb,bs,d,k", [(1, 8, 16, 32, 3), (2, 24, 8, 64, 6)])
def test_scatter_blocks_hkv(h, nb, bs, d, k):
    pool = jax.random.normal(key(22), (h, nb, bs, d), jnp.float32)
    new = jax.random.normal(key(23), (h, k, bs, d), jnp.float32)
    dest = jax.random.choice(key(24), nb, (k,), replace=False)
    out = ops.scatter_blocks_hkv(pool, new, dest)
    want = ref.scatter_blocks_hkv(pool, new, dest)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_scatter_gather_hkv_roundtrip_preserves_other_blocks():
    pool = jax.random.normal(key(25), (2, 16, 8, 32), jnp.float32)
    new = jax.random.normal(key(26), (2, 3, 8, 32), jnp.float32)
    dest = jnp.array([1, 7, 15], jnp.int32)
    pool2 = ops.scatter_blocks_hkv(pool, new, dest)
    np.testing.assert_array_equal(
        np.asarray(ops.gather_blocks_hkv(pool2, dest)), np.asarray(new))
    untouched = [b for b in range(16) if b not in (1, 7, 15)]
    np.testing.assert_array_equal(np.asarray(pool2[:, untouched]),
                                  np.asarray(pool[:, untouched]))


# ---------------------------------------------------------------------------
# block_score (Quest cuboid upper bound)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,nb,d", [(1, 4, 1, 16, 64), (2, 8, 2, 40, 64),
                                           (3, 6, 3, 130, 128)])
def test_block_score(b, hq, hkv, nb, d):
    q = jax.random.normal(key(7), (b, hq, d))
    mn = jax.random.normal(key(8), (b, hkv, nb, d))
    mx = mn + jnp.abs(jax.random.normal(key(9), (b, hkv, nb, d)))
    out = ops.block_score(q, mn, mx)
    want = ref.block_score(q, mn, mx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# sparse_decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,nb,bs,d,k",
                         [(1, 4, 1, 8, 32, 64, 4), (2, 8, 2, 40, 32, 64, 8),
                          (2, 14, 2, 16, 16, 128, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_decode_attention(b, hq, hkv, nb, bs, d, k, dtype):
    q = jax.random.normal(key(10), (b, hq, d), jnp.float32).astype(dtype)
    kp = jax.random.normal(key(11), (b, hkv, nb, bs, d),
                           jnp.float32).astype(dtype)
    vp = jax.random.normal(key(12), (b, hkv, nb, bs, d),
                           jnp.float32).astype(dtype)
    bi = jnp.tile(jnp.arange(k, dtype=jnp.int32)[None, None], (b, hkv, 1))
    sv = jnp.ones((b, hkv, k), bool)
    cl = jnp.full((b,), nb * bs - 3, jnp.int32)   # last block partially valid
    out = ops.sparse_decode_attention(q, kp, vp, bi, sv, cl)
    want = ref.sparse_decode_attention(q, kp, vp, bi, sv, cl)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_sparse_decode_attention_invalid_selection_masked():
    """Invalid selections (sel_valid=False) must not affect the output."""
    b, hq, hkv, nb, bs, d, k = 1, 4, 1, 8, 16, 32, 4
    q = jax.random.normal(key(13), (b, hq, d))
    kp = jax.random.normal(key(14), (b, hkv, nb, bs, d))
    vp = jax.random.normal(key(15), (b, hkv, nb, bs, d))
    cl = jnp.full((b,), nb * bs, jnp.int32)
    bi = jnp.array([[[0, 1, 2, 3]]], jnp.int32)
    sv_all = jnp.array([[[True, True, True, False]]])
    out_masked = ref.sparse_decode_attention(q, kp, vp, bi, sv_all, cl)
    bi3 = jnp.array([[[0, 1, 2, 0]]], jnp.int32)   # 4th points elsewhere
    out_masked2 = ref.sparse_decode_attention(q, kp, vp, bi3, sv_all, cl)
    np.testing.assert_allclose(np.asarray(out_masked),
                               np.asarray(out_masked2), rtol=1e-6)


# ---------------------------------------------------------------------------
# flash_prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,sq,sk,hq,hkv,d",
                         [(1, 64, 64, 4, 1, 64), (2, 128, 128, 8, 2, 64),
                          (1, 96, 96, 2, 2, 128)])
def test_flash_prefill(b, sq, sk, hq, hkv, d):
    q = jax.random.normal(key(16), (b, sq, hq, d))
    k = jax.random.normal(key(17), (b, sk, hkv, d))
    v = jax.random.normal(key(18), (b, sk, hkv, d))
    out = ops.flash_prefill(q, k, v, q_tile=32, k_tile=32)
    want = ref.flash_prefill(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_prefill_q_offset():
    """Chunked continuation: q starts at absolute position q_offset."""
    b, s, hq, hkv, d = 1, 64, 4, 2, 32
    q = jax.random.normal(key(19), (b, 16, hq, d))
    k = jax.random.normal(key(20), (b, s, hkv, d))
    v = jax.random.normal(key(21), (b, s, hkv, d))
    out = ops.flash_prefill(q, k, v, q_offset=48, q_tile=16, k_tile=16)
    want = ref.flash_prefill(q, k, v, q_offset=48)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# interpret vs Mosaic: decided by the platform
# ---------------------------------------------------------------------------

def test_interpret_mode_follows_the_platform(monkeypatch):
    from repro.kernels import platform
    assert platform.resolve_interpret() is (jax.default_backend() == "cpu")
    assert platform.resolve_interpret(False) is False
    assert platform.resolve_interpret(True) is True
    monkeypatch.setattr(platform.jax, "default_backend", lambda: "tpu")
    assert platform.resolve_interpret() is False
    monkeypatch.setattr(platform.jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        platform.resolve_interpret()
