"""The public quantization surface: ``quantize(params_post, params_base, qcfg)``.

One entry point owns the parameter-tree walk, the skip policy, the exact
global delta-metric aggregation (partial sums combined across leaves), and
the storage-vs-dequant emission; the per-leaf math is delegated to a
:class:`Quantizer` resolved from the method registry:

  ``"absmax"``        AbsMax baseline (search collapsed to alpha = 1)
  ``"daq"``           paper Alg. 1 scale search, metric from ``qcfg.metric``
  ``"daq-per-block"`` beyond-paper independent alpha per block/channel
  ``"smoothquant"``   activation-aware equalization, fixed alpha = 0.5
  ``"awq"``           activation-aware equalization, alpha grid by output MSE

Calibration-based methods receive activation statistics through the
``calibrate`` hook (pass ``model=``/``spec=`` or a precomputed ``calib=``
list); data-free methods ignore those arguments.  The legacy
``repro.core.daq.quantize_tree`` / ``absmax_tree`` are deprecated shims over
this function.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import QuantConfig
from repro.core import metrics as M
from repro.core.policy import path_str, should_quantize
from repro.core.search import SearchResult
from repro.quant_runtime.qparams import QuantizedTensor
from repro.quantize.registry import get_method
from repro.telemetry.trace import span


# ---------------------------------------------------------------------------
# Per-leaf context + method protocol
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LeafContext:
    """Everything a :class:`Quantizer` sees for one eligible leaf."""
    name: str                    # joined key path, e.g. "stack/L0/attn/wq"
    w_post: jnp.ndarray          # post-trained weight (>= 2-D)
    w_base: jnp.ndarray          # base weight, same shape
    qcfg: QuantConfig            # method-resolved config


class Quantizer:
    """Base class / protocol for registered quantization methods.

    Subclasses implement ``prepare`` (per-leaf) and may override:

      * ``resolve_config(qcfg)`` — normalize the config before the walk
        (e.g. AbsMax collapses every search knob);
      * ``calibrate(model, params, spec, n_batches=...)`` — produce
        activation statistics for calibration-based methods;
      * ``set_calibration(calib)`` — install (possibly precomputed) stats.
    """

    name: str = ""
    requires_calibration: bool = False

    def resolve_config(self, qcfg: QuantConfig) -> QuantConfig:
        return qcfg

    def calibrate(self, model, params, spec, *, n_batches: int = 2) -> Any:
        return None

    def set_calibration(self, calib: Any) -> None:
        pass

    def prepare(self, ctx: LeafContext) -> SearchResult:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------

@dataclass
class QuantReport:
    per_leaf: dict[str, dict] = field(default_factory=dict)
    global_chosen: dict[str, float] = field(default_factory=dict)
    global_default: dict[str, float] = field(default_factory=dict)
    n_quantized: int = 0
    n_skipped: int = 0
    quantized_bytes: int = 0
    original_bytes: int = 0
    method: str = ""
    host_reads: int = 0          # blocking device -> host reads made

    def summary(self) -> str:
        g, d = self.global_chosen, self.global_default
        lines = [
            f"quantized {self.n_quantized} tensors ({self.n_skipped} skipped), "
            f"{self.original_bytes / 1e6:.1f} MB -> {self.quantized_bytes / 1e6:.1f} MB",
            f"  delta_l2   : {d.get('delta_l2', 0):.4g} -> {g.get('delta_l2', 0):.4g}",
            f"  sign_rate  : {d.get('sign_rate', 0):.4f} -> {g.get('sign_rate', 0):.4f}",
            f"  cosine     : {d.get('cosine', 0):.4f} -> {g.get('cosine', 0):.4f}",
            f"  mse        : {d.get('mse', 0):.4g} -> {g.get('mse', 0):.4g}",
        ]
        if self.method:
            lines.insert(0, f"method: {self.method}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

_LEAF_METRICS = ("mse", "sign_rate", "cosine", "delta_l2")


def _read(report: QuantReport, x, get=float):
    """One blocking device -> host read, ``get(x)``, counted in
    ``report.host_reads``."""
    report.host_reads += 1
    return get(x)


def _scalar_sum(report: QuantReport, x) -> float:
    return _read(report, jnp.sum(x))


def _mean_metric(report: QuantReport, d: dict, m: str) -> float:
    """Per-leaf metric: mean over stacked layers when the leaf was vmapped."""
    return _read(report, jnp.mean(d[m]))


def quantize(params_post: Any, params_base: Any = None,
             qcfg: QuantConfig | None = None, *, mode: str = "dequant",
             out_dtype: str = "float32", method: str | None = None,
             model=None, spec=None, calib: Any = None,
             calib_batches: int = 2) -> tuple[Any, QuantReport]:
    """Quantize every eligible leaf of ``params_post``.

    Args:
      params_post: pytree of post-trained weights.
      params_base: matching pytree of base weights for the delta-aware
        objectives; ``None`` uses ``params_post`` itself (zero delta —
        reconstruction-only regime, delta metrics degenerate).
      qcfg: :class:`QuantConfig`; ``qcfg.method`` selects the algorithm.
      mode: ``"dequant"`` returns float weights (evaluation / benchmarks);
        ``"storage"`` returns :class:`QuantizedTensor` nodes (serving).
      out_dtype: dtype of emitted weights (dequant) / dequantization target
        (storage).
      method: registry-name override of ``qcfg.method``.
      model, spec: forwarded to the method's ``calibrate`` hook when the
        method requires calibration and no ``calib`` was given.
      calib: precomputed calibration statistics (skips ``calibrate``).
      calib_batches: batches for the ``calibrate`` hook.

    Returns:
      ``(quantized_tree, QuantReport)`` — the report carries per-leaf alphas
      and exact global delta metrics at both the chosen and default scales.
    """
    if qcfg is None:
        qcfg = QuantConfig()
    if mode not in ("dequant", "storage"):
        raise ValueError(f"mode must be 'dequant' or 'storage', got {mode!r}")
    name = method or qcfg.method
    quantizer: Quantizer = get_method(name)()
    qcfg = quantizer.resolve_config(qcfg)
    if params_base is None:
        params_base = params_post

    if quantizer.requires_calibration:
        if calib is None and (model is None) != (spec is None):
            raise ValueError(
                f"method {name!r} requires calibration: pass BOTH model= "
                "and spec= (or a precomputed calib= list)")
        if calib is None and model is not None:
            calib = quantizer.calibrate(model, params_post, spec,
                                        n_batches=calib_batches)
        quantizer.set_calibration(calib)

    report = QuantReport(method=name)
    post_leaves, treedef = jax.tree_util.tree_flatten_with_path(params_post)
    base_leaves = jax.tree_util.tree_leaves(params_base)
    if len(post_leaves) != len(base_leaves):
        raise ValueError("post/base parameter trees differ in structure")

    agg_c = {k: 0.0 for k in M.PARTIAL_KEYS}
    agg_d = {k: 0.0 for k in M.PARTIAL_KEYS}

    out_leaves = []
    for (path, w_post), w_base in zip(post_leaves, base_leaves):
        leaf_name = path_str(path)
        if not should_quantize(leaf_name, w_post, qcfg.skip_patterns):
            report.n_skipped += 1
            out_leaves.append(w_post)
            continue
        with span("quantize", "leaf", leaf=leaf_name):
            with span("quantize", "search"):
                res = quantizer.prepare(LeafContext(leaf_name, w_post,
                                                    w_base, qcfg))
            report.n_quantized += 1
            report.original_bytes += w_post.size * w_post.dtype.itemsize
            with span("quantize", "report"):
                for k in M.PARTIAL_KEYS:
                    agg_c[k] += _scalar_sum(report, res.chosen[k])
                    agg_d[k] += _scalar_sum(report, res.default[k])
                report.per_leaf[leaf_name] = {
                    "alpha": _read(report, res.alpha, jax.device_get),
                    "chosen": {m: _mean_metric(report, res.chosen, m)
                               for m in _LEAF_METRICS},
                    "default": {m: _mean_metric(report, res.default, m)
                                for m in _LEAF_METRICS},
                    "shape": tuple(w_post.shape),
                }
            qt = QuantizedTensor(data=res.w_q, scale=res.scale, fmt=qcfg.fmt,
                                 granularity=qcfg.granularity,
                                 block_size=qcfg.block_size,
                                 out_dtype=out_dtype, eq_scale=res.eq_scale)
            report.quantized_bytes += qt.nbytes()
            # dequant mode: Q_s(W_post) rebuilt from storage, bit-exact
            out_leaves.append(qt if mode == "storage" else qt.dequantize())

    with span("quantize", "global"):
        for agg, dst in ((agg_c, report.global_chosen),
                         (agg_d, report.global_default)):
            parts = {k: jnp.asarray(v) for k, v in agg.items()}
            for k, v in M.metrics_from_partials(parts).items():
                dst[k] = _read(report, v)
    return jax.tree_util.tree_unflatten(treedef, out_leaves), report
