"""Registry parity: repro.quantize.quantize vs the legacy entry points.

* ``method="daq"`` must reproduce legacy ``quantize_tree`` outputs (alpha,
  dequantized weights, global metrics) bit-exactly across granularities.
* ``method="absmax"`` must collapse *every* search knob (incl. the fused
  kernel sweep and per-block alpha) to a plain alpha=1 baseline.
* ``"smoothquant"`` / ``"awq"`` through the registry must match the study
  script's original equalization math on a small tree.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import QuantConfig, get_arch, reduced
from repro.core.formats import get_format
from repro.core.granularity import absmax_scale, apply_qdq
from repro.quantize import available_methods, get_method, quantize

KEY = jax.random.PRNGKey(0)


def _pair_tree(seed=0, delta=0.002):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    post = {"blk": {"w": jax.random.normal(k1, (48, 64)) * 0.05,
                    "stack": jax.random.normal(k2, (3, 32, 48)) * 0.05},
            "norm_w": jnp.ones((48,))}
    base = jax.tree.map(
        lambda p: p - delta * jax.random.normal(KEY, p.shape)
        if p.ndim >= 2 else p, post)
    return post, base


def _legacy(fn_name, *args, **kw):
    from repro.core import daq
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return getattr(daq, fn_name)(*args, **kw)


# ---------------------------------------------------------------------------
# Registry surface
# ---------------------------------------------------------------------------

def test_registry_lists_builtin_methods():
    methods = available_methods()
    for m in ("absmax", "daq", "daq-per-block", "smoothquant", "awq"):
        assert m in methods
    with pytest.raises(KeyError, match="unknown quantization method"):
        get_method("nope")


def test_method_resolution_config_vs_override():
    post, base = _pair_tree()
    q = QuantConfig(method="daq", metric="sign", granularity="channel")
    # explicit method= overrides qcfg.method
    _, rep = quantize(post, base, q, method="absmax")
    assert rep.method == "absmax"
    for leaf in rep.per_leaf.values():
        assert np.all(np.asarray(leaf["alpha"]) == 1.0)
    # qcfg.method alone selects the algorithm
    _, rep2 = quantize(post, base, dataclasses.replace(q, method="absmax"))
    assert rep2.global_chosen == rep.global_chosen


# ---------------------------------------------------------------------------
# DAQ parity with the legacy tree walk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gran", ["tensor", "channel", "block"])
def test_daq_matches_legacy_quantize_tree(gran):
    post, base = _pair_tree()
    q = QuantConfig(metric="sign", granularity=gran, block_size=32,
                    alpha_min=0.8, alpha_max=1.25)
    new_tree, new_rep = quantize(post, base, q, method="daq")
    old_tree, old_rep = _legacy("quantize_tree", post, base, q)
    assert new_rep.global_chosen == old_rep.global_chosen
    assert new_rep.global_default == old_rep.global_default
    assert new_rep.n_quantized == old_rep.n_quantized
    assert new_rep.n_skipped == old_rep.n_skipped
    for name in old_rep.per_leaf:
        np.testing.assert_array_equal(np.asarray(new_rep.per_leaf[name]["alpha"]),
                                      np.asarray(old_rep.per_leaf[name]["alpha"]))
    for a, b in zip(jax.tree.leaves(new_tree), jax.tree.leaves(old_tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_daq_walk_matches_handrolled_reference():
    """The walk itself (skip policy, partial-sum aggregation, emission) is
    pinned against an independent hand-rolled implementation — the legacy
    quantize_tree is now a shim over the code under test, so shim-parity
    alone can't catch porting bugs in the walk."""
    from repro.core import metrics as M
    from repro.core.granularity import dequantize_stored
    from repro.core.policy import path_str, should_quantize
    from repro.core.search import search_scale

    post, base = _pair_tree()
    q = QuantConfig(metric="cosine", granularity="block", block_size=32)
    got_tree, got_rep = quantize(post, base, q, method="daq")

    keys = ("sq_err", "n_sign_match", "dot", "dp_sq", "dq_sq", "count")
    agg_c = {k: 0.0 for k in keys}
    agg_d = {k: 0.0 for k in keys}
    exp_leaves, n_q, n_skip = {}, 0, 0
    flat, _ = jax.tree_util.tree_flatten_with_path(post)
    base_leaves = jax.tree_util.tree_leaves(base)
    for (path, wp), wb in zip(flat, base_leaves):
        name = path_str(path)
        if not should_quantize(name, wp, q.skip_patterns):
            n_skip += 1
            exp_leaves[name] = wp
            continue
        n_q += 1
        if wp.ndim == 2:
            res = search_scale(wp, wb, q)
            w_dq = dequantize_stored(res.w_q, res.scale, q.granularity,
                                     get_format(q.fmt), q.block_size,
                                     jnp.float32)
        else:  # Alg. 1's per-layer loop
            res = jax.lax.map(lambda pb: search_scale(*pb, q), (wp, wb))
            w_dq = jnp.stack([
                dequantize_stored(res.w_q[t], res.scale[t], q.granularity,
                                  get_format(q.fmt), q.block_size,
                                  jnp.float32)
                for t in range(wp.shape[0])])
        exp_leaves[name] = w_dq
        for k in keys:
            agg_c[k] += float(jnp.sum(res.chosen[k]))
            agg_d[k] += float(jnp.sum(res.default[k]))
    exp_chosen = {k: float(v) for k, v in M.metrics_from_partials(
        {k: jnp.asarray(v) for k, v in agg_c.items()}).items()}
    exp_default = {k: float(v) for k, v in M.metrics_from_partials(
        {k: jnp.asarray(v) for k, v in agg_d.items()}).items()}

    assert got_rep.n_quantized == n_q and got_rep.n_skipped == n_skip
    np.testing.assert_allclose(
        [got_rep.global_chosen[k] for k in sorted(exp_chosen)],
        [exp_chosen[k] for k in sorted(exp_chosen)], rtol=1e-6)
    np.testing.assert_allclose(
        [got_rep.global_default[k] for k in sorted(exp_default)],
        [exp_default[k] for k in sorted(exp_default)], rtol=1e-6)
    got_flat, _ = jax.tree_util.tree_flatten_with_path(got_tree)
    for path, leaf in got_flat:
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(exp_leaves[path_str(path)],
                                                 np.float32))


def test_daq_storage_matches_legacy():
    post, base = _pair_tree()
    q = QuantConfig(metric="cosine", granularity="block", block_size=32)
    new_tree, _ = quantize(post, base, q, mode="storage",
                           out_dtype="bfloat16")
    old_tree, _ = _legacy("quantize_tree", post, base, q, mode="storage",
                          out_dtype="bfloat16")
    node_new = new_tree["blk"]["w"]
    node_old = old_tree["blk"]["w"]
    np.testing.assert_array_equal(np.asarray(node_new.data, np.float32),
                                  np.asarray(node_old.data, np.float32))
    np.testing.assert_array_equal(np.asarray(node_new.scale),
                                  np.asarray(node_old.scale))
    assert node_new.eq_scale is None


# ---------------------------------------------------------------------------
# AbsMax collapses ALL search knobs
# ---------------------------------------------------------------------------

def test_absmax_clears_fused_kernel_and_per_block():
    """A caller with fused-sweep / per-block flags set must still get a
    plain alpha=1 AbsMax baseline (regression: the legacy absmax_tree left
    use_fused_kernel on, running a fused sweep inside the baseline)."""
    post, base = _pair_tree()
    hot = QuantConfig(granularity="block", block_size=32, metric="sign",
                      use_fused_kernel=True, per_block_alpha=True)
    plain = QuantConfig(granularity="block", block_size=32, metric="sign")
    t_hot, r_hot = quantize(post, base, hot, method="absmax")
    t_plain, r_plain = quantize(post, base, plain, method="absmax")
    assert r_hot.global_chosen == r_plain.global_chosen
    for name, leaf in r_hot.per_leaf.items():
        assert np.all(np.asarray(leaf["alpha"]) == 1.0), name
    for a, b in zip(jax.tree.leaves(t_hot), jax.tree.leaves(t_plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # chosen == default: there was no search
    assert r_hot.global_chosen == r_hot.global_default


def test_absmax_tree_shim_matches_registry():
    post, base = _pair_tree()
    q = QuantConfig(granularity="channel", use_fused_kernel=True)
    _, r_new = quantize(post, base, q, method="absmax")
    _, r_old = _legacy("absmax_tree", post, base, q)
    assert r_new.global_chosen == r_old.global_chosen


def test_legacy_shims_warn():
    post, base = _pair_tree()
    q = QuantConfig(granularity="channel")
    from repro.core.daq import quantize_tree
    with pytest.warns(DeprecationWarning):
        quantize_tree(post, base, q)


# ---------------------------------------------------------------------------
# SmoothQuant / AWQ parity with the original study-script math
# ---------------------------------------------------------------------------

def _ref_equalize_2d(w2d, qcfg, mode, amax=None):
    """The study script's original per-leaf math (pre-registry), verbatim."""
    fmt = get_format(qcfg.fmt)
    w2d = w2d.astype(jnp.float32)
    in_dim = w2d.shape[0]
    if amax is None:
        amax = jnp.ones((in_dim,), jnp.float32)
    a = jnp.maximum(amax.astype(jnp.float32), 1e-6)
    wmax = jnp.maximum(jnp.max(jnp.abs(w2d), axis=1), 1e-6)

    def qdq_scaled(s_vec):
        ws = w2d * s_vec[:, None]
        sc = absmax_scale(ws, qcfg.granularity, fmt, qcfg.block_size)
        return apply_qdq(ws, sc, qcfg.granularity, fmt,
                         qcfg.block_size) / s_vec[:, None]

    if mode == "smoothquant":
        s = jnp.sqrt(a) / jnp.sqrt(wmax)
    else:
        best, best_err = None, jnp.inf
        for alpha in (0.0, 0.25, 0.5, 0.75, 1.0):
            s_try = jnp.maximum(a ** alpha / wmax ** (1 - alpha), 1e-6)
            err = jnp.sum(((qdq_scaled(s_try) - w2d) * a[:, None]) ** 2)
            if best is None or float(err) < float(best_err):
                best, best_err = s_try, err
        s = best
    s = jnp.maximum(s / jnp.maximum(jnp.max(s), 1e-6), 1e-4)
    return qdq_scaled(s)


@pytest.mark.parametrize("mode", ["smoothquant", "awq"])
def test_equalized_methods_match_study_reference(mode):
    post, base = _pair_tree(seed=2)
    q = QuantConfig(method=mode, granularity="channel")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # unit-stats fallback warning
        tree, rep = quantize(post, base, q)
    ref_w = _ref_equalize_2d(post["blk"]["w"], q, mode)
    np.testing.assert_allclose(np.asarray(tree["blk"]["w"]),
                               np.asarray(ref_w), rtol=0, atol=1e-7)
    ref_stack = jnp.stack([_ref_equalize_2d(post["blk"]["stack"][t], q, mode)
                           for t in range(3)])
    np.testing.assert_allclose(np.asarray(tree["blk"]["stack"]),
                               np.asarray(ref_stack), rtol=0, atol=1e-7)
    # skip policy still applies
    assert rep.n_skipped >= 1
    assert np.array_equal(np.asarray(tree["norm_w"]),
                          np.asarray(post["norm_w"]))


@pytest.mark.parametrize("mode", ["smoothquant", "awq"])
def test_equalized_storage_dequant_agree(mode):
    post, base = _pair_tree(seed=3)
    q = QuantConfig(method=mode, granularity="channel")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        deq, _ = quantize(post, base, q, mode="dequant")
        sto, rep = quantize(post, base, q, mode="storage",
                            out_dtype="float32")
    node = sto["blk"]["stack"]
    assert node.eq_scale is not None and node.eq_scale.shape == (3, 32)
    np.testing.assert_allclose(np.asarray(node.dequantize()),
                               np.asarray(deq["blk"]["stack"]), atol=1e-6)
    assert rep.quantized_bytes < rep.original_bytes


def test_calibration_stats_match_by_weight_identity():
    """Same-shaped weights must each get THEIR OWN activation stats
    (regression: the old study script matched stats to leaves by a
    per-shape FIFO, scrambling wq/wo, gate/up, and stacked layers)."""
    from repro.quant_runtime.qlinear import weight_fingerprint
    k = jax.random.split(KEY, 4)
    w_a = jax.random.normal(k[0], (32, 32)) * 0.05
    w_b = jax.random.normal(k[1], (32, 32)) * 0.05         # same shape as a
    w_s = jax.random.normal(k[2], (2, 32, 32)) * 0.05      # stacked
    post = {"a": w_a, "b": w_b, "s": w_s}
    base = jax.tree.map(lambda p: p * 0.99, post)
    amax = {"a": jnp.full((32,), 4.0), "b": jnp.full((32,), 0.25),
            "s0": jnp.linspace(0.5, 2.0, 32), "s1": jnp.linspace(2.0, 0.5, 32)}
    calib = [((32, 32), weight_fingerprint(w_a), amax["a"]),
             ((32, 32), weight_fingerprint(w_b), amax["b"]),
             ((32, 32), weight_fingerprint(w_s[0]), amax["s0"]),
             ((32, 32), weight_fingerprint(w_s[1]), amax["s1"])]
    q = QuantConfig(method="smoothquant", granularity="channel")
    _, rep = quantize(post, base, q, calib=calib)

    def expected_s(w2d, a):
        wmax = jnp.maximum(jnp.max(jnp.abs(w2d), axis=1), 1e-6)
        s = jnp.sqrt(jnp.maximum(a, 1e-6)) / jnp.sqrt(wmax)
        return jnp.maximum(s / jnp.maximum(jnp.max(s), 1e-6), 1e-4)

    np.testing.assert_allclose(np.asarray(rep.per_leaf["a"]["alpha"]),
                               np.asarray(expected_s(w_a, amax["a"])),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(rep.per_leaf["b"]["alpha"]),
                               np.asarray(expected_s(w_b, amax["b"])),
                               atol=1e-6)
    # stacked leaf: slice t gets slice t's stats, not call-order leftovers
    got = np.asarray(rep.per_leaf["s"]["alpha"])
    np.testing.assert_allclose(got[0], np.asarray(expected_s(w_s[0], amax["s0"])),
                               atol=1e-6)
    np.testing.assert_allclose(got[1], np.asarray(expected_s(w_s[1], amax["s1"])),
                               atol=1e-6)


def test_equalized_calibration_through_model():
    """End-to-end: stats collected via the calibrate hook on a real model
    change the equalization (vs unit stats) and keep metrics finite."""
    from repro.data import LanguageSpec
    from repro.models import build_model
    cfg = reduced(get_arch("glm4-9b"))
    model = build_model(cfg)
    params = model.init(KEY)
    base = jax.tree.map(lambda p: p * 0.995 if p.ndim >= 2 else p, params)
    spec = LanguageSpec(vocab=cfg.vocab_size)
    q = QuantConfig(method="smoothquant", granularity="channel")
    with warnings.catch_warnings():
        # a properly calibrated run must not cry wolf (embed tables never
        # route through matmul and are exempt from the miss warning)
        warnings.simplefilter("error", UserWarning)
        calibrated, rep_c = quantize(params, base, q, model=model, spec=spec,
                                     calib_batches=1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        unit, rep_u = quantize(params, base, q)
    assert rep_c.n_quantized == rep_u.n_quantized > 0
    for v in rep_c.global_chosen.values():
        assert np.isfinite(v)
    # real activation stats must actually steer s away from the unit case
    diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in
             zip(jax.tree.leaves(calibrated), jax.tree.leaves(unit))]
    assert max(diffs) > 0


def test_quantize_defaults_base_to_post():
    post, _ = _pair_tree()
    _, rep = quantize(post, qcfg=QuantConfig(granularity="channel"))
    # base defaults to post: zero delta, reconstruction-only regime.
    # delta_l2 reduces to the plain quantization error and stays finite.
    assert rep.n_quantized > 0
    for v in rep.global_chosen.values():
        assert np.isfinite(v)
    assert rep.global_chosen["mse"] > 0


def test_quantize_rejects_bad_mode():
    post, base = _pair_tree()
    with pytest.raises(ValueError, match="mode"):
        quantize(post, base, QuantConfig(), mode="nope")


def test_calibration_requires_model_and_spec_together():
    post, base = _pair_tree()
    q = QuantConfig(method="smoothquant", granularity="channel")
    with pytest.raises(ValueError, match="BOTH model= and spec="):
        quantize(post, base, q, model=object())
    with pytest.raises(ValueError, match="BOTH model= and spec="):
        quantize(post, base, q, spec=object())


def test_empty_calib_warns_like_none():
    post, base = _pair_tree()
    q = QuantConfig(method="smoothquant", granularity="channel")
    with pytest.warns(UserWarning, match="no calibration stats"):
        quantize(post, base, q, calib=[])


def test_calibration_miss_warns_once():
    """Stats present but a leaf unmatched -> one loud warning, not silent
    unit-scale degradation."""
    from repro.quant_runtime.qlinear import weight_fingerprint
    post, base = _pair_tree()
    other = jax.random.normal(KEY, (48, 64))  # fingerprint matches nothing
    calib = [((48, 64), weight_fingerprint(other), jnp.ones((48,)))]
    q = QuantConfig(method="smoothquant", granularity="channel")
    with pytest.warns(UserWarning, match="no calibration record matches"):
        quantize(post, base, q, calib=calib)
