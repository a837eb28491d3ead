"""Equivalence tests for the §Perf optimizations (EXPERIMENTS.md):
GQA repeat-sharding, fp8 KV cache, fused-search kernel integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime
from repro.configs import get_arch, reduced
from repro.models import build_model

KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    runtime.flags["force_kv_repeat"] = 0
    runtime.flags["kv_cache_dtype"] = "bfloat16"


def _pair(cfg, model, params, S=20):
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S + 1), 0,
                              cfg.vocab_size)
    l1, c1 = model.prefill(params, {"tokens": toks[:, :S]}, cache_len=S + 4)
    d1, _ = model.decode_step(params, toks[:, S:S + 1], c1)
    loss, _ = model.loss_fn(params, {"tokens": toks[:, :-1],
                                     "labels": toks[:, 1:]})
    return l1, d1, loss, c1


def test_kv_repeat_bit_exact():
    """Repeat-sharded caches/attention are numerically identical."""
    cfg = reduced(get_arch("glm4-9b"))
    model = build_model(cfg)
    params = model.init(KEY)
    l1, d1, loss1, c1 = _pair(cfg, model, params)

    runtime.flags["force_kv_repeat"] = 2
    model2 = build_model(cfg)
    l2, d2, loss2, c2 = _pair(cfg, model2, params)

    assert c2["stack"]["L0"]["k"].shape[3] == 2 * c1["stack"]["L0"]["k"].shape[3]
    np.testing.assert_array_equal(np.asarray(l1, np.float32),
                                  np.asarray(l2, np.float32))
    np.testing.assert_array_equal(np.asarray(d1, np.float32),
                                  np.asarray(d2, np.float32))
    np.testing.assert_array_equal(np.asarray(loss1), np.asarray(loss2))


def test_fp8_kv_cache_close():
    """fp8 KV cache: decode logits within E4M3 noise of bf16 cache."""
    cfg = reduced(get_arch("glm4-9b"))
    model = build_model(cfg)
    params = model.init(KEY)
    _, d1, _, c1 = _pair(cfg, model, params)

    runtime.flags["kv_cache_dtype"] = "float8_e4m3fn"
    model2 = build_model(cfg)
    _, d2, _, c2 = _pair(cfg, model2, params)

    assert c2["stack"]["L0"]["k"].dtype == jnp.float8_e4m3fn
    rel = float(jnp.max(jnp.abs(d1 - d2)) / (jnp.max(jnp.abs(d1)) + 1e-9))
    assert rel < 0.15, rel


def test_fp8_kv_cache_ring():
    """fp8 cache + SWA ring compose."""
    cfg = reduced(get_arch("mixtral-8x22b"))
    runtime.flags["kv_cache_dtype"] = "float8_e4m3fn"
    model = build_model(cfg)
    params = model.init(KEY)
    W = cfg.sliding_window
    toks = jax.random.randint(KEY, (2, W + 6), 0, cfg.vocab_size)
    _, cache = model.prefill(params, {"tokens": toks[:, :W + 5]},
                             cache_len=W)
    logits, _ = model.decode_step(params, toks[:, -1:], cache)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_quantized_abstract_specs_match_concrete():
    """quantized_param_specs layout == quantize_tree storage layout."""
    from repro.configs import QuantConfig
    from repro.core.daq import quantize_tree
    from repro.launch.specs import quantized_param_specs
    cfg = reduced(get_arch("glm4-9b"))
    model = build_model(cfg)
    params = model.init(KEY)
    base = jax.tree.map(lambda p: p * 0.99 if p.ndim >= 2 else p, params)
    q = QuantConfig()
    concrete, _ = quantize_tree(params, base, q, mode="storage")
    abstract = quantized_param_specs(
        jax.eval_shape(model.init, KEY), q)
    ca = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(lambda x: (x.shape, str(x.dtype)), concrete))[0]
    ab = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(lambda x: (x.shape, str(x.dtype)), abstract))[0]
    assert len(ca) == len(ab)
    for (pa, va), (pb, vb) in zip(ca, ab):
        assert va == vb, (pa, va, vb)
