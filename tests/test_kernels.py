"""Pallas kernel allclose tests: shape/dtype sweeps against the pure-jnp
oracles in repro.kernels.ref (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


# full shape sweep in f32; bf16 tolerance is covered on one shape per
# causal mode (each cell is a separate pallas-interpret compile)
@pytest.mark.parametrize("T,Hq,Hkv,D,dtype,causal", [
    (128, 4, 4, 64, jnp.float32, True),
    (128, 4, 4, 64, jnp.float32, False),
    (128, 8, 2, 64, jnp.float32, True),
    (128, 8, 2, 64, jnp.float32, False),
    (128, 6, 1, 32, jnp.float32, True),
    (128, 6, 1, 32, jnp.float32, False),
    (128, 4, 4, 64, jnp.bfloat16, True),
    (128, 8, 2, 64, jnp.bfloat16, False),
])
def test_flash_attention(T, Hq, Hkv, D, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B = 2
    q = jax.random.normal(ks[0], (B, T, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    o = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("S,Hq,Hkv,D,window,dtype", [
    (256, 4, 4, 64, None, jnp.float32),
    (256, 8, 2, 64, 128, jnp.float32),
    (256, 4, 1, 32, 64, jnp.float32),
    (256, 4, 4, 64, None, jnp.bfloat16),
])
def test_decode_attention(S, Hq, Hkv, D, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B = 3
    q = jax.random.normal(ks[0], (B, 1, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    # ring-buffer-like positions with empty slots
    kv_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    kv_pos = jnp.where(kv_pos < S - 37, kv_pos, -1)
    q_pos = jnp.full((B, 1), S - 40, jnp.int32)
    o = ops.decode_attention(q, k, v, q_pos, kv_pos, window=window, block_kv=128)
    o_ref = ref.decode_attention_ref(q, k, v, q_pos, kv_pos, window=window)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("shape", [(512, 128), (3, 256, 64), (2, 4, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(2), shape, dtype)
    sc = jax.random.normal(jax.random.PRNGKey(3), shape[-1:], jnp.float32)
    o = ops.rmsnorm(x, sc, block_rows=64)
    o_ref = ref.rmsnorm_ref(x, sc)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("T,H,D,chunk", [(128, 2, 64, 32), (96, 4, 32, 32), (128, 1, 64, 64)])
def test_wkv6_vs_sequential(T, H, D, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    B = 2
    r = jax.random.normal(ks[0], (B, T, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, D)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, D)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, D)) * 0.1
    o = ops.wkv6(r, k, v, logw, u, chunk=chunk)
    o_ref = ref.wkv6_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-4, rtol=2e-4)


@pytest.mark.slow  # default 128/256 block sizes need larger (slower) shapes
def test_flash_attention_multiblock_default_blocks():
    """Cross-block online-softmax carry with the kernels' DEFAULT block
    sizes (the fast-tier sweep exercises multi-block grids via explicit
    64-wide blocks)."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64))
    k = jax.random.normal(ks[1], (2, 256, 2, 64))
    v = jax.random.normal(ks[2], (2, 256, 2, 64))
    o = ops.flash_attention(q, k, v, causal=True)  # default blocks
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_decode_attention_multiblock_default_blocks():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 64))
    k = jax.random.normal(ks[1], (2, 512, 2, 64))
    v = jax.random.normal(ks[2], (2, 512, 2, 64))
    kv_pos = jnp.broadcast_to(jnp.arange(512)[None], (2, 512)).astype(jnp.int32)
    q_pos = jnp.full((2, 1), 511, jnp.int32)
    o = ops.decode_attention(q, k, v, q_pos, kv_pos)  # default block_kv
    o_ref = ref.decode_attention_ref(q, k, v, q_pos, kv_pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_fallback_on_ragged_shapes():
    """Non-divisible block shapes raise an error naming the shape and the
    blocks; the kernel never quietly computes the reference instead."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 100, 2, 32))
    k = jax.random.normal(ks[1], (1, 100, 2, 32))
    v = jax.random.normal(ks[2], (1, 100, 2, 32))
    with pytest.raises(ValueError, match=r"\(1, 100, 2, 32\).*blocks \(64, 64\)"):
        ops.flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)


@pytest.mark.parametrize("kernel,call", [
    ("decode_attention", lambda x, pos: ops.decode_attention(
        x[:, :1], x, x, pos[:, :1], pos, block_kv=64)),
    ("rmsnorm", lambda x, pos: ops.rmsnorm(x, jnp.ones((32,)), block_rows=64)),
    ("wkv6", lambda x, pos: ops.wkv6(x, x, x, -jnp.ones_like(x), jnp.ones((2, 32)),
                                     chunk=64)),
])
def test_kernels_raise_on_ragged_shapes(kernel, call):
    x = jnp.ones((1, 100, 2, 32))
    pos = jnp.broadcast_to(jnp.arange(100, dtype=jnp.int32)[None], (1, 100))
    with pytest.raises(ValueError, match=kernel + r": shape .* blocks"):
        call(x, pos)


def test_sdpa_keeps_ragged_shapes_on_xla():
    """The attention entry picks XLA for shapes the kernels cannot tile,
    so the pallas impl still answers (and matches the XLA path)."""
    from repro.models import attention

    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (1, 200, 2, 32))
    k = jax.random.normal(ks[1], (1, 200, 2, 32))
    v = jax.random.normal(ks[2], (1, 200, 2, 32))
    pos = jnp.broadcast_to(jnp.arange(200, dtype=jnp.int32)[None], (1, 200))
    assert not ops.flash_attention_fits(200, 200)
    assert not ops.decode_attention_fits(300)
    with attention.force_impl("pallas"):
        o = attention.sdpa(q, k, v, pos, pos, causal=True)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5, rtol=1e-5)
