"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _qkv(B, H, K, S, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, K, S, D), dtype)
    v = jax.random.normal(ks[2], (B, K, S, D), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,D,g", [(128, 32, 1), (256, 64, 4), (128, 128, 2)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64), (False, None)])
def test_flash_attention_sweep(S, D, g, dtype, causal, window):
    B, K = 2, 2
    H = K * g
    q, k, v = _qkv(B, H, K, S, D, dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="pallas", block_q=64, block_k=64)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


def test_flash_attention_grad_matches_reference():
    B, H, K, S, D = 1, 4, 2, 128, 32
    q, k, v = _qkv(B, H, K, S, D, jnp.float32)

    gp = jax.grad(lambda q, k, v: ops.flash_attention(
        q, k, v, impl="pallas", block_q=64, block_k=64).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: ref.flash_attention_ref(q, k, v).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,D,valid", [(256, 64, 256), (256, 64, 100), (128, 128, 1)])
def test_decode_attention_sweep(S, D, valid, dtype):
    B, H, K = 2, 4, 2
    q = jax.random.normal(KEY, (B, H, D), dtype)
    _, k, v = _qkv(B, H, K, S, D, dtype)
    out = ops.decode_attention(q, k, v, jnp.int32(valid), impl="pallas", block_s=64)
    exp = ref.decode_attention_ref(q, k, v, jnp.int32(valid))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,pos,layer", [(128, 127, 0), (256, 100, 2), (128, 0, 1)])
def test_mla_decode_sweep(S, pos, layer, dtype):
    B, H, L, rank, rope = 2, 4, 3, 128, 64
    ks = jax.random.split(KEY, 4)
    q_lat = jax.random.normal(ks[0], (B, H, rank), dtype)
    q_pe = jax.random.normal(ks[1], (B, H, rope), dtype)
    c_kv = jax.random.normal(ks[2], (L, B, S, rank), dtype)
    k_pe = jax.random.normal(ks[3], (L, B, rope, S), dtype)
    args = (q_lat, q_pe, c_kv, k_pe, jnp.int32(layer), jnp.int32(pos), 0.1)
    out = ops.mla_decode(*args, impl="pallas")
    exp = ref.mla_decode_ref(*args)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("L,W,bw", [(32, 256, 128), (64, 512, 512), (17, 256, 256)])
def test_rglru_scan_sweep(L, W, bw):
    B = 2
    a = jax.nn.sigmoid(jax.random.normal(KEY, (B, L, W)))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, L, W))
    h0 = jax.random.normal(jax.random.PRNGKey(2), (B, W))
    hp, hTp = ops.rglru_scan(a, x, h0, impl="pallas", block_w=bw)
    hr, hTr = ref.rglru_scan_ref(a, x, h0)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(hr), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hTp), np.asarray(hTr), atol=1e-5, rtol=1e-5)


@given(
    st.integers(min_value=1, max_value=24),
    st.integers(min_value=1, max_value=3).map(lambda i: 128 * i),
)
@settings(max_examples=12, deadline=None)
def test_rglru_scan_property(L, W):
    B = 1
    a = jax.nn.sigmoid(jax.random.normal(KEY, (B, L, W)))
    x = jax.random.normal(jax.random.PRNGKey(3), (B, L, W))
    h0 = jnp.zeros((B, W))
    hp, _ = ops.rglru_scan(a, x, h0, impl="pallas", block_w=128)
    hr, _ = ref.rglru_scan_ref(a, x, h0)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(hr), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("L,Di,N,bd", [(32, 128, 8, 64), (16, 256, 16, 128)])
def test_ssm_scan_sweep(L, Di, N, bd):
    B = 2
    a = jax.nn.sigmoid(jax.random.normal(KEY, (B, L, Di, N)))
    bx = jax.random.normal(jax.random.PRNGKey(1), (B, L, Di, N))
    c = jax.random.normal(jax.random.PRNGKey(2), (B, L, N))
    h0 = jax.random.normal(jax.random.PRNGKey(3), (B, Di, N))
    yp, hTp = ops.ssm_scan(a, bx, c, h0, impl="pallas", block_d=bd)
    yr, hTr = ref.ssm_scan_ref(a, bx, c, h0)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(hTp), np.asarray(hTr), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("R,D,dtype", [(64, 256, jnp.float32), (128, 512, jnp.bfloat16)])
def test_rmsnorm_sweep(R, D, dtype):
    x = jax.random.normal(KEY, (R, D), dtype)
    s = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (D,), jnp.float32)
    out = ops.rmsnorm(x, s, impl="pallas", block_r=32)
    exp = ref.rmsnorm_ref(x, s)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


def test_pallas_attention_in_model_matches_reference_model():
    """attention_impl='pallas' end-to-end inside the transformer."""
    import dataclasses

    from repro.models import ModelConfig, forward, init_params, model_pspecs

    base = ModelConfig(
        name="t", family="dense", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab_size=128, head_dim=32, remat="none", dtype="float32",
        attn_block_q=64,
    )
    params = init_params(KEY, model_pspecs(base))
    toks = jax.random.randint(KEY, (2, 128), 0, 128)
    lg_ref, _ = jax.jit(lambda p, t: forward(base, p, t))(params, toks)
    cfg_pl = dataclasses.replace(base, attention_impl="pallas")
    lg_pl, _ = jax.jit(lambda p, t: forward(cfg_pl, p, t))(params, toks)
    np.testing.assert_allclose(np.asarray(lg_pl), np.asarray(lg_ref), atol=2e-4, rtol=2e-4)
