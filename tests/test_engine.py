"""Serving-engine tests: greedy parity with the old host loop, slot reuse,
sampler behavior, sharded smoke, quantized embedding gather."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import QuantConfig, get_arch, reduced
from repro.data import LanguageSpec, sample_batch
from repro.engine import (Engine, SamplingParams, sample, serve_host_loop)
from repro.models import build_model

KEY = jax.random.PRNGKey(0)


def _setup(arch="glm4-9b"):
    cfg = reduced(get_arch(arch))
    model = build_model(cfg)
    params = model.init(KEY)
    spec = LanguageSpec(vocab=cfg.vocab_size)
    return cfg, model, params, spec


def _prompts(spec, lens):
    return [sample_batch(jax.random.PRNGKey(i), spec, 1, L)[0]
            for i, L in enumerate(lens)]


def test_engine_greedy_token_exact_vs_host_loop():
    """Device-resident K-step decode == old per-token host loop, token for
    token, including slot refills mid-stream."""
    cfg, model, params, spec = _setup()
    prompts = _prompts(spec, [10, 10, 10, 10, 10])
    legacy = serve_host_loop(model, params, prompts, batch=2, gen_tokens=6,
                             cache_len=30)
    eng = Engine(model, params, slots=2, cache_len=30, k_steps=3)
    outs, stats = eng.serve(prompts, gen_tokens=6, return_stats=True)
    assert outs == legacy
    # at most one host sync per K decode steps (plus one per prefill group)
    assert stats["dispatches"] * eng.cfg.k_steps == stats["decode_steps"]
    assert stats["host_syncs"] == stats["dispatches"] + stats["prefill_calls"]


def test_engine_greedy_parity_unequal_lengths_padded_prefill():
    """The single right-padded multi-slot prefill call stays token-exact
    against the legacy batch-1-per-slot prefill."""
    cfg, model, params, spec = _setup()
    prompts = _prompts(spec, [8, 13, 8, 11])
    eng = Engine(model, params, slots=3, cache_len=36, k_steps=2)
    assert eng._can_pad  # dense causal stack -> padded path is in play
    legacy = serve_host_loop(model, params, prompts, batch=3, gen_tokens=5,
                             cache_len=36)
    assert eng.serve(prompts, gen_tokens=5) == legacy


def test_engine_bucketed_prefill_for_ring_ssm_and_moe():
    """SWA-ring, Mamba-state and MoE configs refuse padding (pad tokens
    would corrupt ring slots / SSM state) and group prompts by exact
    length — outputs still match the legacy loop."""
    for arch in ("mixtral-8x22b", "mamba2-780m",
                 "deepseek-v3"):               # moe, no sliding window
        cfg, model, params, spec = _setup(arch)
        prompts = _prompts(spec, [9, 12, 9])
        eng = Engine(model, params, slots=2, cache_len=34, k_steps=2)
        assert not eng._can_pad
        legacy = serve_host_loop(model, params, prompts, batch=2,
                                 gen_tokens=4, cache_len=34)
        assert eng.serve(prompts, gen_tokens=4) == legacy


def test_engine_slot_reuse_more_requests_than_slots():
    cfg, model, params, spec = _setup()
    prompts = _prompts(spec, [8] * 7)
    eng = Engine(model, params, slots=2, cache_len=24, k_steps=4)
    outs, stats = eng.serve(prompts, gen_tokens=5, return_stats=True)
    assert len(outs) == 7
    assert all(len(o) == 5 for o in outs)
    # 7 requests through 2 slots forces at least ceil(7/2) admission rounds
    assert stats["prefill_calls"] >= 4
    assert stats["tokens"] == 35


def test_sampler_modes_and_determinism():
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (4, 32))
    # greedy == argmax regardless of key
    g = sample(logits, key, SamplingParams())
    assert jnp.array_equal(g, jnp.argmax(logits, -1).astype(jnp.int32))
    # top_k=1 collapses the categorical onto the argmax
    t1 = sample(logits, key, SamplingParams(greedy=False, temperature=0.7,
                                            top_k=1))
    assert jnp.array_equal(t1, g)
    # top-k draws never leave the per-row top-k set
    sp = SamplingParams(greedy=False, temperature=1.5, top_k=5)
    topk = jax.lax.top_k(logits, 5)[1]
    draws = jax.vmap(lambda k: sample(logits, k, sp))(
        jax.random.split(key, 32))
    assert bool(jnp.all((draws[..., None] == topk[None]).any(-1)))
    # fixed key -> deterministic; different key -> a different draw exists
    a = sample(logits, key, sp)
    assert jnp.array_equal(a, sample(logits, key, sp))
    with pytest.raises(ValueError):
        SamplingParams(greedy=False, temperature=0.0)


def test_engine_sampling_deterministic_under_fixed_seed():
    cfg, model, params, spec = _setup()
    prompts = _prompts(spec, [9, 9, 9])
    sp = SamplingParams(greedy=False, temperature=0.8, top_k=8)
    eng = Engine(model, params, slots=2, cache_len=26, k_steps=3, sampling=sp)
    a = eng.serve(prompts, gen_tokens=6, seed=7)
    assert a == eng.serve(prompts, gen_tokens=6, seed=7)
    assert all(len(o) == 6 for o in a)


def test_engine_sharded_smoke_host_mesh():
    """Sharded serving on a host mesh reproduces unsharded outputs, and
    quantized storage/scale leaves inherit the dense weight's layout."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.sharding import params_shardings
    from repro.quantize import quantize

    cfg, model, params, spec = _setup()
    prompts = _prompts(spec, [10, 10, 10])
    mesh = make_host_mesh()
    ref = Engine(model, params, slots=2, cache_len=26,
                 k_steps=2).serve(prompts, gen_tokens=4)
    eng = Engine(model, params, slots=2, cache_len=26, k_steps=2, mesh=mesh)
    assert eng.serve(prompts, gen_tokens=4) == ref

    # quantized tree: .../wq/data and .../wq/scale follow the dense spec
    base = jax.tree.map(lambda p: p * 0.99 if p.ndim >= 2 else p, params)
    qparams, _ = quantize(params, base,
                          QuantConfig(method="absmax", granularity="channel"),
                          mode="storage", out_dtype="bfloat16")
    dense_sh = params_shardings(jax.eval_shape(lambda: params), cfg, mesh)
    quant_sh = params_shardings(jax.eval_shape(lambda: qparams), cfg, mesh)
    d = dense_sh["stack"]["L0"]["attn"]["wq"].spec
    q = quant_sh["stack"]["L0"]["attn"]["wq"].data.spec
    assert tuple(q) == tuple(d)
    # quantized params also serve sharded
    qeng = Engine(model, qparams, slots=2, cache_len=26, k_steps=2, mesh=mesh)
    qref = Engine(model, qparams, slots=2, cache_len=26,
                  k_steps=2).serve(prompts, gen_tokens=4)
    assert qeng.serve(prompts, gen_tokens=4) == qref


def test_qlinear_take_gathers_rows_before_dequant():
    """take() on a quantized table matches dequantize()[ids] for every
    granularity, with and without an equalization vector."""
    from repro.core.formats import get_format
    from repro.core.granularity import absmax_scale, quantize_store
    from repro.quant_runtime import qlinear
    from repro.quant_runtime.qparams import QuantizedTensor

    fmt = get_format("fp8_e4m3")
    table = jax.random.normal(KEY, (40, 24), jnp.float32)
    ids = jnp.asarray([[0, 5, 39], [17, 5, 2]], jnp.int32)
    for gran, bs in (("tensor", 128), ("channel", 128), ("block", 16)):
        scale = absmax_scale(table, gran, fmt, bs)
        q = quantize_store(table, scale, gran, fmt, bs)
        for eq in (None, jnp.abs(jax.random.normal(
                jax.random.PRNGKey(1), (40,))) + 0.5):
            qt = QuantizedTensor(q, scale, fmt="fp8_e4m3", granularity=gran,
                                 block_size=bs, out_dtype="bfloat16",
                                 eq_scale=eq)
            got = qlinear.take(qt, ids)
            want = qt.dequantize()[ids]
            assert got.shape == want.shape == (2, 3, 24)
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                rtol=0, atol=0, err_msg=f"{gran} eq={eq is not None}")


def test_make_serve_step_deprecation_shim():
    cfg, model, params, spec = _setup()
    with pytest.warns(DeprecationWarning):
        from repro.launch.steps import make_serve_step
        step = make_serve_step(model)
    cache = model.init_cache(2, 16)
    toks = jnp.ones((2, 1), jnp.int32)
    nxt, logits, new_cache = jax.jit(step)(params, toks, cache)
    assert nxt.shape == (2, 1)
    assert jnp.array_equal(nxt[:, 0], jnp.argmax(logits, -1))
