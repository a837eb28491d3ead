"""Coarse-to-fine scale search (paper Algorithm 1) + beyond-paper variants.

The paper-faithful search optimizes ONE alpha multiplier per weight tensor
(applied on top of the per-granularity AbsMax default scales s0) via a coarse
uniform grid over [alpha_min, alpha_max] followed by a fine grid around the
best coarse candidate.  alpha = 1 (the AbsMax default) is always the initial
incumbent (Alg. 1 lines 4-6), so the search never returns a candidate that
scores worse than AbsMax *on the chosen metric*.

Beyond-paper extension (``per_block_alpha=True``): an independent alpha per
block / channel, selected on a dense grid by the per-block objective.  For
SignRate and MSE the objective is separable across blocks, so the per-block
argmax is the *global* optimum over the per-block candidate grid — strictly
at least as good as any shared alpha on the same grid.  For Cosine the
per-block selection optimizes block-local cosine (documented in DESIGN.md).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import QuantConfig
from repro.core import metrics as M
from repro.core.formats import get_format
from repro.core.granularity import (absmax_scale, apply_qdq, pad_to_blocks,
                                    quantize_store, to_blocked)


@dataclass
class SearchResult:
    """Result of quantizing one weight tensor.  The dequantized weights are
    not kept: ``QuantizedTensor(w_q, scale, ...).dequantize()`` reproduces
    Q_s(W_post) bit-exactly when a caller wants them."""
    alpha: jnp.ndarray          # chosen multiplier(s): scalar or per-block
    scale: jnp.ndarray          # final scale(s) = alpha * s0
    w_q: jnp.ndarray            # storage representation (fp8/int8), layout of W
    chosen: dict                # metrics + partial sums at chosen alpha
    default: dict               # metrics + partial sums at alpha=1 (AbsMax)
    eq_scale: jnp.ndarray | None = None  # per-in-channel equalization vector
                                         # (SmoothQuant/AWQ); w_q stores W*s


jax.tree_util.register_dataclass(
    SearchResult,
    data_fields=["alpha", "scale", "w_q", "chosen", "default", "eq_scale"],
    meta_fields=[],
)

# Candidates are scored over row blocks of at most this many fp32 bytes, so
# scoring one candidate on a [151552, 4096] embedding holds a few blocks'
# temporaries instead of several fp32 copies of the whole leaf.  2**23
# elements also keeps each block's sign-match count an exact fp32 integer.
ROW_BLOCK_BYTES = 32 << 20


def _candidate_grid(qcfg: QuantConfig) -> jnp.ndarray:
    """Dense grid used by the per-block variant (coarse+fine budget)."""
    n = qcfg.n_coarse + qcfg.n_fine
    return jnp.linspace(qcfg.alpha_min, qcfg.alpha_max, n)


def _row_block(shape: tuple[int, int], qcfg: QuantConfig) -> int:
    """Rows per scoring block: the largest divisor of I whose fp32 block
    fits ``ROW_BLOCK_BYTES``, in whole quant blocks for block granularity
    (whose scale rows must line up; a ragged I is scored in one block)."""
    I, O = shape
    unit = 1
    if qcfg.granularity == "block":
        unit = qcfg.block_size if I % qcfg.block_size == 0 else I
    n_units = I // unit
    fit = max(1, ROW_BLOCK_BYTES // (unit * O * 4))
    d = max(k for k in range(1, min(fit, n_units) + 1) if n_units % k == 0)
    return d * unit


def leaf_partials(w_post, w_base, scale, qcfg: QuantConfig) -> dict:
    """Whole-leaf :func:`repro.core.metrics.partial_sums` of ``dp = W_post -
    W_base`` and ``dq = Q_scale(W_post) - W_base``, summed over row blocks
    (equal to the one-shot sums up to summation order)."""
    fmt = get_format(qcfg.fmt)
    I = w_post.shape[0]
    R = _row_block(w_post.shape, qcfg)

    def one(wp, wb, s):
        wp = wp.astype(jnp.float32)
        wb = wb.astype(jnp.float32)
        wq = apply_qdq(wp, s, qcfg.granularity, fmt, qcfg.block_size)
        return M.partial_sums(wp - wb, wq - wb, (0, 1))

    if R == I:
        return one(w_post, w_base, scale)
    rows_per_scale = R // qcfg.block_size

    def body(acc, i):
        rows = lambda w: jax.lax.dynamic_slice_in_dim(w, i * R, R, 0)
        s = (jax.lax.dynamic_slice_in_dim(scale, i * rows_per_scale,
                                          rows_per_scale, 0)
             if qcfg.granularity == "block" else scale)
        part = one(rows(w_post), rows(w_base), s)
        return jax.tree.map(jnp.add, acc, part), None

    zero = {k: jnp.float32(0) for k in M.PARTIAL_KEYS}
    acc, _ = jax.lax.scan(body, zero, jnp.arange(I // R))
    return acc


def with_metrics(partials: dict) -> dict:
    """Partial sums plus the whole-tensor metrics they determine: the
    common currency of ``SearchResult.chosen`` / ``.default`` across all
    registered methods (``repro.quantize`` sums the partials over leaves
    into exact global model metrics)."""
    return {**partials, **M.metrics_from_partials(partials)}


@partial(jax.jit, static_argnames=("qcfg",))
def search_scale(w_post: jnp.ndarray, w_base: jnp.ndarray,
                 qcfg: QuantConfig) -> SearchResult:
    """Paper Algorithm 1 on a single 2-D weight (jit-compiled).

    Dispatches to the per-block variant when ``qcfg.per_block_alpha`` and to
    the fused one-HBM-pass Pallas sweep when ``qcfg.use_fused_kernel``
    (block fp8 only; same argmax by construction — tests assert equality).
    Candidates are scored from row-blocked partial sums, so the working set
    is a few row blocks, not fp32 copies of the whole leaf.
    """
    if qcfg.per_block_alpha:
        return _search_per_block(w_post, w_base, qcfg)
    if qcfg.use_fused_kernel and qcfg.granularity == "block" \
            and qcfg.fmt == "fp8_e4m3":
        return _search_fused(w_post, w_base, qcfg)

    fmt = get_format(qcfg.fmt)
    s0 = absmax_scale(w_post, qcfg.granularity, fmt, qcfg.block_size)

    def eval_fn(a):
        p = leaf_partials(w_post, w_base, a * s0, qcfg)
        return M.objective_from_partials(qcfg.metric, p, qcfg.hybrid_lambda)

    # --- init: alpha = 1 (Alg. 1 lines 4-6) ---
    best_alpha = jnp.float32(1.0)
    best_m = eval_fn(best_alpha)

    # --- coarse stage (lines 7-15) ---
    coarse = jnp.linspace(qcfg.alpha_min, qcfg.alpha_max, qcfg.n_coarse)
    coarse_m = jax.lax.map(eval_fn, coarse)
    c_idx = jnp.argmax(coarse_m)
    take_c = coarse_m[c_idx] > best_m
    best_alpha = jnp.where(take_c, coarse[c_idx], best_alpha)
    best_m = jnp.maximum(coarse_m[c_idx], best_m)

    # --- fine stage (lines 16-24) ---
    delta = qcfg.resolved_fine_delta()
    lo = jnp.maximum(qcfg.alpha_min, best_alpha - delta)
    hi = jnp.minimum(qcfg.alpha_max, best_alpha + delta)
    fine = jnp.linspace(lo, hi, qcfg.n_fine)
    fine_m = jax.lax.map(eval_fn, fine)
    f_idx = jnp.argmax(fine_m)
    take_f = fine_m[f_idx] > best_m
    best_alpha = jnp.where(take_f, fine[f_idx], best_alpha)

    return _finalize(w_post, w_base, best_alpha, s0, qcfg)


def _finalize(w_post, w_base, alpha, s0, qcfg: QuantConfig) -> SearchResult:
    fmt = get_format(qcfg.fmt)
    scale = alpha * s0
    w_q = quantize_store(w_post, scale, qcfg.granularity, fmt, qcfg.block_size)
    chosen = with_metrics(leaf_partials(w_post, w_base, scale, qcfg))
    default = with_metrics(leaf_partials(w_post, w_base, s0, qcfg))
    return SearchResult(alpha=alpha, scale=scale, w_q=w_q,
                        chosen=chosen, default=default)


# ---------------------------------------------------------------------------
# Fused-kernel search: Alg. 1 with the Pallas one-pass candidate sweep.
# ---------------------------------------------------------------------------

def _search_fused(w_post, w_base, qcfg: QuantConfig) -> SearchResult:
    """Same coarse->fine argmax as `search_scale`, but each stage evaluates
    ALL candidates in ONE pass over the weights (kernels/scale_search) —
    ~8x less HBM traffic than re-reading W per candidate (see §Perf)."""
    from repro.kernels.scale_search import ops as K

    s0 = absmax_scale(w_post, "block", get_format(qcfg.fmt), qcfg.block_size)

    def stage_best(alphas):
        parts = K.sweep(w_post, w_base, alphas, block_size=qcfg.block_size)
        objs = K.objective_values(parts, qcfg.metric, qcfg.hybrid_lambda)
        idx = jnp.argmax(objs)
        return alphas[idx], objs[idx]

    # stage 1: incumbent alpha=1 rides along with the coarse grid
    coarse = jnp.concatenate([jnp.float32([1.0]),
                              jnp.linspace(qcfg.alpha_min, qcfg.alpha_max,
                                           qcfg.n_coarse)])
    best_alpha, _ = stage_best(coarse)
    # stage 2: fine grid around the best candidate (+ incumbent)
    delta = qcfg.resolved_fine_delta()
    lo = jnp.maximum(qcfg.alpha_min, best_alpha - delta)
    hi = jnp.minimum(qcfg.alpha_max, best_alpha + delta)
    fine = jnp.concatenate([best_alpha[None],
                            jnp.linspace(lo, hi, qcfg.n_fine)])
    best_alpha, _ = stage_best(fine)
    return _finalize(w_post, w_base, best_alpha, s0, qcfg)


# ---------------------------------------------------------------------------
# Beyond-paper: independent alpha per block / channel on a dense grid.
# ---------------------------------------------------------------------------

def _search_per_block(w_post, w_base, qcfg: QuantConfig) -> SearchResult:
    fmt = get_format(qcfg.fmt)
    w_post = w_post.astype(jnp.float32)
    w_base = w_base.astype(jnp.float32)
    dp = w_post - w_base
    s0 = absmax_scale(w_post, qcfg.granularity, fmt, qcfg.block_size)
    grid = jnp.concatenate([jnp.float32([1.0]), _candidate_grid(qcfg)])

    if qcfg.granularity == "channel":
        reduce_axes = (0,)
        def per_cand(a):
            wq = apply_qdq(w_post, a * s0, "channel", fmt)
            p = M.partial_sums(dp, wq - w_base, reduce_axes)
            return M.objective_from_partials(qcfg.metric, p, qcfg.hybrid_lambda)
        objs = jax.lax.map(per_cand, grid)              # [n_cand, 1, O]
        idx = jnp.argmax(objs, axis=0)                  # [1, O]
        alpha = grid[idx]                               # [1, O]
    elif qcfg.granularity == "block":
        bs = qcfg.block_size
        wp, _ = pad_to_blocks(w_post, bs)
        wbse, _ = pad_to_blocks(w_base, bs)
        dpb = to_blocked(wp, bs) - to_blocked(wbse, bs)
        def per_cand(a):
            wqb = to_blocked(wp, bs)
            from repro.core.formats import qdq as _qdq
            wqb = _qdq(wqb, a * s0, fmt)
            p = M.partial_sums(dpb, wqb - to_blocked(wbse, bs), (1, 3))
            return M.objective_from_partials(qcfg.metric, p, qcfg.hybrid_lambda)
        objs = jax.lax.map(per_cand, grid)              # [n_cand, I/bs, O/bs]
        idx = jnp.argmax(objs, axis=0)                  # [I/bs, O/bs]
        alpha = grid[idx][:, None, :, None]             # broadcastable vs blocked view
    else:  # tensor granularity: per-block == shared
        reduce_axes = None
        def per_cand(a):
            wq = apply_qdq(w_post, a * s0, "tensor", fmt)
            return M.objective(qcfg.metric, dp, wq - w_base, qcfg.hybrid_lambda)
        objs = jax.lax.map(per_cand, grid)
        alpha = grid[jnp.argmax(objs)]

    return _finalize(w_post, w_base, alpha, s0, qcfg)
