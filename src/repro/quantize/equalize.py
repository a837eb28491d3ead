"""Registered calibration-based methods: SmoothQuant and AWQ (weight-only).

Both quantize ``Q(W diag(s)) / diag(s)`` — numerically the same space as W,
so the delta metrics stay well-defined (a bonus over the papers' absorbed
formulation).  The per-input-channel equalization vector ``s`` comes from
activation statistics collected by :func:`collect_input_stats`, which flows
through the :meth:`Quantizer.calibrate` hook; without calibration the
methods fall back to unit activation scales (with a warning).

In storage mode the equalization vector rides along on the emitted
:class:`QuantizedTensor` (``eq_scale``), so equalized trees serve through
the same ``qlinear`` path as DAQ trees.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.configs.base import QuantConfig
from repro.core import metrics as M
from repro.core.formats import Format, get_format
from repro.core.granularity import absmax_scale, apply_qdq, quantize_store
from repro.core.search import SearchResult, leaf_partials, with_metrics
from repro.quantize.api import LeafContext, Quantizer
from repro.quantize.registry import register


def collect_input_stats(model, params, spec, n_batches: int = 2) -> list:
    """Eager unrolled forward; returns [(w_shape, w_fingerprint, absmax[in])].

    Records are keyed by the weight's value fingerprint
    (:func:`repro.quant_runtime.qlinear.weight_fingerprint`), not by shape —
    same-shaped weights (wq/wo, gate/up, ...) would otherwise collide.
    Raw per-call records are returned; repeated calls of one weight (across
    batches or call sites) are max-merged by ``set_calibration``.
    """
    from repro import runtime
    from repro.data.synthetic import _full_logits, sample_batch
    from repro.quant_runtime import qlinear

    prev_unroll = runtime.flags["unroll_layers"]
    runtime.flags["unroll_layers"] = True
    qlinear.RECORD = []
    try:
        for i in range(n_batches):
            toks = sample_batch(jax.random.PRNGKey(500 + i), spec, 4, 64)
            _full_logits(model, params,
                         {"tokens": toks[:, :-1], "labels": toks[:, 1:]})
        return qlinear.RECORD
    finally:
        qlinear.RECORD = None
        runtime.flags["unroll_layers"] = prev_unroll


class _EqualizeQuantizer(Quantizer):
    """Shared machinery: stats matching, Q(W·s)/s, delta metrics."""

    requires_calibration = True

    def __init__(self):
        self._stats: dict[tuple, jnp.ndarray] = {}
        self._warned_miss = False

    def calibrate(self, model, params, spec, *, n_batches: int = 2) -> list:
        return collect_input_stats(model, params, spec, n_batches)

    def set_calibration(self, calib) -> None:
        # stats match leaves by (shape, value-fingerprint) — exact, no
        # call-order bookkeeping; fingerprint collisions max-merge
        self._stats = {}
        if not calib:  # None or empty: nothing was recorded at all
            warnings.warn(
                f"{self.name}: no calibration stats (pass model=/spec= or "
                "calib=); equalization falls back to unit activation scales",
                stacklevel=3)
            return
        for shape, fp, amax in calib:
            key = (tuple(shape), fp)
            prev = self._stats.get(key)
            self._stats[key] = amax if prev is None \
                else jnp.maximum(prev, amax)

    def _equalization(self, w2d: jnp.ndarray, a: jnp.ndarray,
                      wmax: jnp.ndarray, qcfg: QuantConfig,
                      fmt: Format) -> jnp.ndarray:
        raise NotImplementedError

    def _prepare_2d(self, wp, wb, qcfg: QuantConfig,
                    name: str = "?") -> SearchResult:
        from repro.quant_runtime.qlinear import weight_fingerprint
        fmt = get_format(qcfg.fmt)
        wp32 = wp.astype(jnp.float32)
        wb32 = wb.astype(jnp.float32)
        dp = wp32 - wb32

        amax = self._stats.get((tuple(wp.shape), weight_fingerprint(wp)))
        if amax is None:
            # a miss with stats present means the forward saw different
            # weight values than this leaf — surface it once rather than
            # silently degrading to unit scales everywhere.  Embedding
            # tables are exempt: they go through qlinear.take, never
            # qlinear.matmul, so no record can exist for them by design.
            if self._stats and not self._warned_miss \
                    and "embed" not in name.lower():
                self._warned_miss = True
                warnings.warn(
                    f"{self.name}: no calibration record matches leaf "
                    f"{name!r} {tuple(wp.shape)}; it (and any further "
                    "unmatched leaves) equalize with unit activation scales",
                    stacklevel=2)
            amax = jnp.ones((wp32.shape[0],), jnp.float32)
        a = jnp.maximum(amax.astype(jnp.float32), 1e-6)
        wmax = jnp.maximum(jnp.max(jnp.abs(wp32), axis=1), 1e-6)
        s = self._equalization(wp32, a, wmax, qcfg, fmt)
        s = jnp.maximum(s / jnp.maximum(jnp.max(s), 1e-6), 1e-4)

        ws = wp32 * s[:, None]
        scale = absmax_scale(ws, qcfg.granularity, fmt, qcfg.block_size)
        w_q = quantize_store(ws, scale, qcfg.granularity, fmt, qcfg.block_size)
        w_dq = apply_qdq(ws, scale, qcfg.granularity, fmt,
                         qcfg.block_size) / s[:, None]
        # default baseline: plain AbsMax at the same granularity, no
        # equalization — mirrors SearchResult.default for the DAQ methods
        s0 = absmax_scale(wp32, qcfg.granularity, fmt, qcfg.block_size)
        chosen = M.partial_sums(dp, w_dq - wb32, (0, 1))
        return SearchResult(alpha=s, scale=scale, w_q=w_q,
                            chosen=with_metrics(chosen),
                            default=with_metrics(
                                leaf_partials(wp32, wb32, s0, qcfg)),
                            eq_scale=s)

    def prepare(self, ctx: LeafContext) -> SearchResult:
        return self._prepare_nd(ctx.w_post, ctx.w_base, ctx.qcfg, ctx.name)

    def _prepare_nd(self, wp, wb, qcfg: QuantConfig,
                    name: str) -> SearchResult:
        if wp.ndim == 2:
            return self._prepare_2d(wp, wb, qcfg, name)
        # stacked layers: each slice looks up its own stats by fingerprint
        # (python loop — the dict lookup is host-side, so no vmap)
        parts = [self._prepare_nd(wp[t], wb[t], qcfg, f"{name}[{t}]")
                 for t in range(wp.shape[0])]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *parts)


def _qdq_scaled(w2d, s_vec, qcfg: QuantConfig, fmt: Format):
    ws = w2d * s_vec[:, None]
    sc = absmax_scale(ws, qcfg.granularity, fmt, qcfg.block_size)
    return apply_qdq(ws, sc, qcfg.granularity, fmt,
                     qcfg.block_size) / s_vec[:, None]


@register("smoothquant")
class SmoothQuantQuantizer(_EqualizeQuantizer):
    """Fixed migration strength alpha = 0.5: s = sqrt(a_max) / sqrt(w_max)."""

    def _equalization(self, w2d, a, wmax, qcfg, fmt):
        return jnp.sqrt(a) / jnp.sqrt(wmax)


@register("awq")
class AWQQuantizer(_EqualizeQuantizer):
    """Alpha grid per leaf, picked by activation-weighted output MSE."""

    GRID = (0.0, 0.25, 0.5, 0.75, 1.0)

    def _equalization(self, w2d, a, wmax, qcfg, fmt):
        s_tries = jnp.stack([jnp.maximum(a ** al / wmax ** (1 - al), 1e-6)
                             for al in self.GRID])
        errs = jnp.stack([
            jnp.sum(((_qdq_scaled(w2d, s, qcfg, fmt) - w2d) * a[:, None]) ** 2)
            for s in s_tries])
        return s_tries[jnp.argmin(errs)]
