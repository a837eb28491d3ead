"""Algorithm 1 invariants + fused/per-block variants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import QuantConfig
from repro.core import metrics as M
from repro.core.formats import get_format
from repro.core.granularity import absmax_scale, apply_qdq, dequantize_stored
from repro.core.search import search_scale


def _dq(res, q):
    """Q_s(W_post) of a search result, rebuilt from its storage form."""
    return dequantize_stored(res.w_q, res.scale, q.granularity,
                             get_format(q.fmt), q.block_size, jnp.float32)


def _pair(seed, shape=(96, 64), delta=0.003):
    key = jax.random.PRNGKey(seed)
    wb = jax.random.normal(key, shape) * 0.05
    wp = wb + jax.random.normal(jax.random.PRNGKey(seed + 1), shape) * delta
    return wp, wb


@pytest.mark.parametrize("metric", ["mse", "sign", "cosine", "hybrid"])
@pytest.mark.parametrize("gran", ["tensor", "channel", "block"])
def test_never_worse_than_absmax(metric, gran):
    """Alg.1 lines 4-6: alpha=1 is the incumbent, so the chosen scale is
    never worse than AbsMax on the chosen metric."""
    wp, wb = _pair(0)
    q = QuantConfig(metric=metric, granularity=gran, block_size=32)
    res = search_scale(wp, wb, q)
    fmt = get_format(q.fmt)
    s0 = absmax_scale(wp, gran, fmt, 32)
    dp = wp - wb
    dq0 = apply_qdq(wp, s0, gran, fmt, 32) - wb
    m_abs = float(M.objective(metric, dp, dq0))
    m_chosen = float(M.objective(metric, dp, _dq(res, q) - wb))
    assert m_chosen >= m_abs - 1e-6


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000))
def test_alpha_within_range(seed):
    wp, wb = _pair(seed)
    q = QuantConfig(metric="cosine", granularity="block", block_size=32,
                    alpha_min=0.8, alpha_max=1.25)
    res = search_scale(wp, wb, q)
    a = float(res.alpha)
    assert 0.8 - 1e-6 <= a <= 1.25 + 1e-6 or abs(a - 1.0) < 1e-6


@pytest.mark.parametrize("metric", ["mse", "sign", "cosine"])
def test_fused_kernel_matches_naive(metric):
    wp, wb = _pair(3, shape=(256, 128))
    q1 = QuantConfig(metric=metric, granularity="block", block_size=128)
    q2 = dataclasses.replace(q1, use_fused_kernel=True)
    r1 = search_scale(wp, wb, q1)
    r2 = search_scale(wp, wb, q2)
    assert abs(float(r1.alpha) - float(r2.alpha)) < 1e-6
    np.testing.assert_array_equal(np.asarray(r1.w_q, np.float32),
                                  np.asarray(r2.w_q, np.float32))


@pytest.mark.parametrize("metric", ["mse", "sign"])
def test_per_block_at_least_as_good(metric):
    """Separable metrics: per-block alpha beats any shared alpha on the
    same candidate grid (beyond-paper extension)."""
    wp, wb = _pair(4, shape=(128, 96))
    q_shared = QuantConfig(metric=metric, granularity="block", block_size=32)
    q_block = dataclasses.replace(q_shared, per_block_alpha=True)
    r_s = search_scale(wp, wb, q_shared)
    r_b = search_scale(wp, wb, q_block)
    dp = wp - wb
    m_s = float(M.objective(metric, dp, _dq(r_s, q_shared) - wb))
    m_b = float(M.objective(metric, dp, _dq(r_b, q_block) - wb))
    assert m_b >= m_s - 1e-6


def test_stacked_leaves_vmapped():
    """[L, I, O] weights get one alpha per layer (Alg. 1 per-layer loop)."""
    from repro.core.daq import quantize_tree
    wp, wb = _pair(5, shape=(3, 64, 48))
    q = QuantConfig(metric="sign", granularity="channel")
    out, report = quantize_tree({"w": wp}, {"w": wb}, q)
    assert np.asarray(report.per_leaf["w"]["alpha"]).shape == (3,)


def test_zero_delta_perfect_metrics():
    """W_post == W_base: cosine undefined-but-safe, sign counts zeros."""
    wb = jax.random.normal(jax.random.PRNGKey(7), (64, 64)) * 0.1
    q = QuantConfig(metric="sign", granularity="channel")
    res = search_scale(wb, wb, q)
    assert np.isfinite(float(res.chosen["cosine"]))


@pytest.mark.parametrize("gran", ["tensor", "channel", "block"])
def test_row_blocked_scoring_matches_one_shot(gran, monkeypatch):
    """Large leaves are scored over row blocks: the partial sums add up to
    the one-block sums (up to summation order; sign counts exactly), so the
    chosen alpha does not move."""
    import repro.core.search as S
    wp, wb = _pair(9, shape=(256, 96))
    q = QuantConfig(metric="sign", granularity=gran, block_size=32)
    fresh = lambda: jax.jit(S.search_scale.__wrapped__,
                            static_argnames=("qcfg",))
    assert S._row_block(wp.shape, q) == 256
    one = fresh()(wp, wb, q)
    monkeypatch.setattr(S, "ROW_BLOCK_BYTES", 32 * 96 * 4)
    assert S._row_block(wp.shape, q) == 32        # 8 blocks of 32 rows
    blocked = fresh()(wp, wb, q)
    assert float(blocked.alpha) == float(one.alpha)
    np.testing.assert_array_equal(np.asarray(blocked.w_q, np.float32),
                                  np.asarray(one.w_q, np.float32))
    for part in ("chosen", "default"):
        a, b = getattr(blocked, part), getattr(one, part)
        assert float(a["n_sign_match"]) == float(b["n_sign_match"])
        for k in ("sq_err", "dot", "dp_sq", "dq_sq", "count"):
            np.testing.assert_allclose(float(a[k]), float(b[k]), rtol=1e-5)
