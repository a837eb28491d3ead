"""System/integration tests: quantize_tree end-to-end, distributed dry-run
(subprocess with fake devices), serving batcher."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import QuantConfig, get_arch, reduced
from repro.core.daq import absmax_tree, quantize_tree
from repro.models import build_model

KEY = jax.random.PRNGKey(0)


def _pair_tree():
    cfg = reduced(get_arch("glm4-9b"))
    model = build_model(cfg)
    post = model.init(KEY)
    base = jax.tree.map(
        lambda p: p + (0.002 * jax.random.normal(KEY, p.shape)).astype(p.dtype)
        if p.ndim >= 2 else p, post)
    return cfg, model, post, base


def test_quantize_tree_skips_norms_and_1d():
    cfg, model, post, base = _pair_tree()
    _, report = quantize_tree(post, base, QuantConfig(granularity="channel"))
    assert report.n_skipped > 0
    for name in report.per_leaf:
        assert "norm" not in name and "bias" not in name


def test_storage_and_dequant_modes_agree():
    cfg, model, post, base = _pair_tree()
    q = QuantConfig(granularity="block", block_size=32, metric="sign")
    deq, _ = quantize_tree(post, base, q, mode="dequant")
    sto, r2 = quantize_tree(post, base, q, mode="storage",
                            out_dtype="float32")
    wq_deq = deq["stack"]["L0"]["attn"]["wq"]
    node = sto["stack"]["L0"]["attn"]["wq"]
    np.testing.assert_allclose(np.asarray(wq_deq, np.float32),
                               np.asarray(node.dequantize(), np.float32),
                               atol=1e-3)
    assert r2.quantized_bytes < r2.original_bytes


def test_daq_beats_absmax_on_its_metric():
    cfg, model, post, base = _pair_tree()
    q = QuantConfig(granularity="block", block_size=32, metric="sign",
                    alpha_min=0.5, alpha_max=2.0)
    _, r_daq = quantize_tree(post, base, q)
    _, r_abs = absmax_tree(post, base, q)
    assert (r_daq.global_chosen["sign_rate"]
            >= r_abs.global_chosen["sign_rate"] - 1e-6)


def test_eq7_mse_search_is_base_agnostic():
    """MSE metric ignores the base model (paper Eq. 7): same alpha with any
    base."""
    from repro.core.search import search_scale
    wp = jax.random.normal(KEY, (64, 64)) * 0.1
    wb1 = jnp.zeros_like(wp)
    wb2 = jax.random.normal(jax.random.PRNGKey(1), (64, 64)) * 0.1
    q = QuantConfig(metric="mse", granularity="channel",
                    alpha_min=0.5, alpha_max=2.0)
    a1 = float(search_scale(wp, wb1, q).alpha)
    a2 = float(search_scale(wp, wb2, q).alpha)
    assert abs(a1 - a2) < 1e-6


def test_compile_cache_prefers_env(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and the code sets nothing."""
    from repro.runtime import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo(monkeypatch):
    """Without the variable the cache goes to the fixed <repo>/.jax_cache."""
    from repro.runtime import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:  # no compile ran in between, so nothing was written there
        jax.config.update("jax_compilation_cache_dir", before)


def test_mini_dryrun_subprocess():
    """The production dry-run machinery on an 8-device fake mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax
from repro.configs import TrainConfig, get_arch, reduced, ShapeConfig
from repro.launch import sharding as SH
from repro.launch.steps import make_train_step
from repro.launch.specs import train_batch_specs, state_specs
from repro.models import build_model

cfg = reduced(get_arch("glm4-9b"))
model = build_model(cfg)
tc = TrainConfig()
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
state = state_specs(model, tc)
shape = ShapeConfig("mini", 64, 8, "train")
batch = train_batch_specs(cfg, shape)
st_sh = {"params": SH.params_shardings(state["params"], cfg, mesh),
         "opt": SH.opt_state_shardings(state["opt"], state["params"], cfg,
                                       mesh)}
b_sh = SH.batch_shardings(batch, mesh)
step = make_train_step(model, tc)
with jax.set_mesh(mesh):
    compiled = jax.jit(step, in_shardings=(st_sh, b_sh),
                       out_shardings=(st_sh, None),
                       donate_argnums=0).lower(state, batch).compile()
print("COMPILED_OK", compiled.memory_analysis().temp_size_in_bytes)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=560)
    assert "COMPILED_OK" in r.stdout, r.stderr[-2000:]


def test_serving_batcher_outputs():
    """Continuous-batching serve(): all requests get gen_tokens tokens."""
    from repro.data import LanguageSpec, sample_batch
    from repro.launch.serve import serve
    cfg = reduced(get_arch("glm4-9b"))
    model = build_model(cfg)
    params = model.init(KEY)
    spec = LanguageSpec(vocab=cfg.vocab_size)
    prompts = [sample_batch(jax.random.PRNGKey(i), spec, 1, 12)[0]
               for i in range(3)]
    outs = serve(model, params, prompts, batch=2, gen_tokens=4, cache_len=24)
    assert len(outs) == 3
    assert all(len(o) == 4 for o in outs)


def test_serve_greedy_matches_plain_decode():
    """The slot batcher reproduces plain greedy decoding per request."""
    from repro.data import LanguageSpec, sample_batch
    from repro.launch.serve import serve
    cfg = reduced(get_arch("glm4-9b"))
    model = build_model(cfg)
    params = model.init(KEY)
    spec = LanguageSpec(vocab=cfg.vocab_size)
    prompt = sample_batch(jax.random.PRNGKey(3), spec, 1, 12)[0]
    outs = serve(model, params, [prompt], batch=2, gen_tokens=4,
                 cache_len=24)
    logits, cache = model.prefill(params, {"tokens": prompt[None]},
                                  cache_len=24)
    ref = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    ref.append(int(tok[0, 0]))
    for _ in range(3):
        logits, cache = model.decode_step(params, tok, cache)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        ref.append(int(tok[0, 0]))
    assert outs[0] == ref
