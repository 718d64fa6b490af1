"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Every kernel is validated against its ref.py oracle across shapes, dtypes,
GQA group sizes, window sizes and block sizes — the repo's kernel contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RTOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------- flash attn

@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 4, 4, 128, 64),        # MHA
    (2, 4, 2, 256, 64),        # GQA 2x
    (1, 8, 2, 128, 32),        # GQA 4x
    (2, 2, 1, 192, 128),       # ragged seq vs block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, hq, hkv, s, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (b, hq, s, d), dtype)
    k = _rand(ks[1], (b, hkv, s, d), dtype)
    v = _rand(ks[2], (b, hkv, s, d), dtype)
    out = ops.attention(q, k, v, causal=True, q_block=64, kv_block=64,
                        backend="interpret")
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=RTOL[dtype], atol=ATOL[dtype])


@pytest.mark.parametrize("window", [64, 128, 192])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (1, 4, 256, 64), jnp.float32)
    k = _rand(ks[1], (1, 2, 256, 64), jnp.float32)
    v = _rand(ks[2], (1, 2, 256, 64), jnp.float32)
    out = ops.attention(q, k, v, causal=True, window=window,
                        q_block=64, kv_block=64, backend="interpret")
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (1, 2, 128, 64), jnp.float32)
    k = _rand(ks[1], (1, 2, 128, 64), jnp.float32)
    v = _rand(ks[2], (1, 2, 128, 64), jnp.float32)
    out = ops.attention(q, k, v, causal=False, q_block=64, kv_block=64,
                        backend="interpret")
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("qb,kb", [(32, 64), (128, 32), (64, 64)])
def test_flash_attention_block_shape_invariance(qb, kb):
    """Output must not depend on the tiling."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (1, 2, 128, 32), jnp.float32)
    k = _rand(ks[1], (1, 2, 128, 32), jnp.float32)
    v = _rand(ks[2], (1, 2, 128, 32), jnp.float32)
    a = ops.attention(q, k, v, q_block=qb, kv_block=kb, backend="interpret")
    b = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------- rmsnorm

@pytest.mark.parametrize("shape", [(8, 128), (3, 5, 256), (1, 1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    x = _rand(k1, shape, dtype)
    w = _rand(k2, shape[-1:], dtype)
    out = ops.rmsnorm(x, w, backend="interpret", block_rows=4)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=RTOL[dtype], atol=ATOL[dtype])


# --------------------------------------------------------------- mamba2 scan

@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 64, 2, 8, 4, 16),
    (2, 128, 3, 16, 8, 32),
    (1, 96, 1, 8, 16, 32),
])
def test_mamba_chunk_scan_sweep(b, s, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = _rand(ks[0], (b, s, h, p), jnp.float32) * 0.5
    bm = _rand(ks[1], (b, s, n), jnp.float32) * 0.5
    cm = _rand(ks[2], (b, s, n), jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand(ks[3], (b, s, h), jnp.float32))
    da = -dt * jnp.exp(_rand(ks[4], (h,), jnp.float32) * 0.1)
    y, hf = ops.mamba_chunk_scan(x, bm, cm, dt, da, chunk=chunk,
                                 backend="interpret")
    y_ref, hf_ref = ref.mamba_chunk_scan_ref(x, bm, cm, dt, da)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_ref),
                               rtol=3e-4, atol=3e-4)


def test_mamba_chunk_invariance():
    """Final state and outputs must not depend on the chunking."""
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    b, s, h, p, n = 1, 128, 2, 8, 8
    x = _rand(ks[0], (b, s, h, p), jnp.float32) * 0.5
    bm = _rand(ks[1], (b, s, n), jnp.float32) * 0.5
    cm = _rand(ks[2], (b, s, n), jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand(ks[3], (b, s, h), jnp.float32))
    da = -dt
    y32, h32 = ops.mamba_chunk_scan(x, bm, cm, dt, da, chunk=32,
                                    backend="interpret")
    y64, h64 = ops.mamba_chunk_scan(x, bm, cm, dt, da, chunk=64,
                                    backend="interpret")
    np.testing.assert_allclose(np.asarray(y32), np.asarray(y64),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h32), np.asarray(h64),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ backend="auto"

@pytest.mark.parametrize("call", [
    lambda x: ops.attention(x, x, x),
    lambda x: ops.rmsnorm(x, x[0, 0, 0]),
    lambda x: ops.mamba_chunk_scan(x, x[:, :, 0], x[:, :, 0], x[..., 0],
                                   x[..., 0], chunk=128),
], ids=["attention", "rmsnorm", "mamba_chunk_scan"])
def test_auto_backend_refuses_off_tpu(call):
    """``auto`` means compiled Pallas on a TPU; elsewhere it must raise
    instead of quietly running the interpreter (tests run on the CPU)."""
    x = jnp.zeros((1, 2, 128, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        call(x)
