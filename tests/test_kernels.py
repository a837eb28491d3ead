"""Pallas kernels vs pure-jnp oracles (interpret mode on the CPU backend),
shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fp8_matmul.kernel import matmul_fp8_pallas
from repro.kernels.fp8_matmul.ref import matmul_fp8_ref
from repro.kernels.fp8_quant.kernel import quantize_fp8_pallas
from repro.kernels.fp8_quant.ops import quantize_fp8
from repro.kernels.fp8_quant.ref import quantize_fp8_ref
from repro.kernels.scale_search.kernel import sweep_partials_pallas
from repro.kernels.scale_search.ref import sweep_partials_ref


@pytest.mark.parametrize("shape,bs", [((256, 128), 128), ((128, 256), 64),
                                      ((384, 384), 128), ((64, 64), 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scale_search_kernel(shape, bs, dtype):
    I, O = shape
    key = jax.random.PRNGKey(I + O)
    wb = (jax.random.normal(key, shape) * 0.05).astype(dtype)
    wp = wb + (jax.random.normal(jax.random.PRNGKey(1), shape)
               * 0.002).astype(dtype)
    wp32, wb32 = wp.astype(jnp.float32), wb.astype(jnp.float32)
    alphas = jnp.linspace(0.8, 1.25, 4)
    nbi, nbo = I // bs, O // bs
    amax = jnp.max(jnp.abs(wp32.reshape(nbi, bs, nbo, bs)), axis=(1, 3))
    s0 = jnp.maximum(amax, 1e-12) / 448.0
    pk = sweep_partials_pallas(wp32, wb32, s0, alphas, block_size=bs)
    pr = sweep_partials_ref(wp32, wb32, s0, alphas, block_size=bs)
    # Tolerances: the sign-match stat is an integer count; fp32
    # associativity / division-order can flip exact-tie elements (bf16
    # inputs produce many exact boundary deltas).  Counts agree to <1%;
    # all continuous stats agree to 1e-4 relative.
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr),
                               rtol=1.2e-2, atol=2.5)
    cont = [0, 2, 3, 4]  # sq_err, dot, dp_sq, dq_sq
    np.testing.assert_allclose(np.asarray(pk)[..., cont],
                               np.asarray(pr)[..., cont],
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("M,K,N", [(64, 256, 256), (128, 128, 384),
                                   (32, 256, 128), (8, 128, 128)])
@pytest.mark.parametrize("xdtype", [jnp.bfloat16, jnp.float32])
def test_fp8_matmul_kernel(M, K, N, xdtype):
    key = jax.random.PRNGKey(M * K + N)
    x = jax.random.normal(key, (M, K)).astype(xdtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.1
    q, s = quantize_fp8(w)
    yk = matmul_fp8_pallas(x, q, s, bm=min(128, M), block=128)
    yr = matmul_fp8_ref(x, q, s, block=128)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("shape,b", [((256, 256), 128), ((128, 384), 128),
                                     ((256, 128), 64), ((64, 192), 64)])
def test_fp8_quant_kernel(shape, b):
    w = jax.random.normal(jax.random.PRNGKey(0), shape) * 0.3
    qk, sk = quantize_fp8_pallas(w, jnp.ones(1), block=b)
    qr, sr = quantize_fp8_ref(w, jnp.ones(1), block=b)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    a = np.asarray(qk, np.float32)
    r = np.asarray(qr, np.float32)
    neq = a != r
    # 1-ulp division differences may flip an fp8 bucket for boundary values
    assert neq.mean() < 1e-4, f"{neq.sum()} mismatches"


def test_fp8_quant_ragged_padding():
    """ops wrapper pads ragged shapes and returns the original layout."""
    w = jax.random.normal(jax.random.PRNGKey(2), (130, 70)) * 0.1
    q, s = quantize_fp8(w, block=64)
    assert q.shape == (130, 70)
    assert s.shape == (-(-130 // 64), -(-70 // 64))
    # dequant error bounded by fp8 resolution
    nbi, nbo = s.shape
    # reconstruct with block scales
    wpad = jnp.pad(w, ((0, 128 - 130 % 128 if False else (-130) % 64),
                       (0, (-70) % 64)))
    dq = (jnp.pad(q.astype(jnp.float32), (((0), (-130) % 64), (0, (-70) % 64)))
          .reshape(nbi, 64, nbo, 64) * s[:, None, :, None]).reshape(
              nbi * 64, nbo * 64)[:130, :70]
    err = jnp.abs(dq - w)
    assert float(jnp.max(err / (jnp.abs(w) + 1e-3))) < 0.2


def test_flash_attention_vs_naive():
    """models/flash.py fwd + grad vs a dense softmax oracle."""
    from repro.models.attention import chunked_attention
    B, S, H, Kv, hd = 2, 24, 4, 2, 8
    G = H // Kv
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Kv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Kv, hd))

    def naive(q, k, v, causal, window, cap):
        kr = jnp.repeat(k, G, 2)
        vr = jnp.repeat(v, G, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / np.sqrt(hd)
        if cap:
            s = cap * jnp.tanh(s / cap)
        qp = jnp.arange(S)[:, None]
        kp = jnp.arange(S)[None, :]
        m = jnp.ones((S, S), bool)
        if causal:
            m = m & (kp <= qp)
        if window:
            m = m & (kp > qp - window)
        s = jnp.where(m[None, None], s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vr)

    for causal, window, cap in [(True, 0, 0.0), (True, 7, 0.0),
                                (False, 0, 0.0), (True, 0, 5.0)]:
        fa = lambda q, k, v: chunked_attention(
            q, k, v, causal=causal, window=window, softcap=cap,
            q_chunk=8, kv_chunk=8)
        np.testing.assert_allclose(
            np.asarray(fa(q, k, v)),
            np.asarray(naive(q, k, v, causal, window, cap)),
            rtol=1e-4, atol=1e-5)
        g1 = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(fa(q, k, v))),
                      (0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            naive(q, k, v, causal, window, cap))), (0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=3e-5)
