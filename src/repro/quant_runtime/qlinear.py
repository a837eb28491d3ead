"""Quantization-aware linear application.

Every matmul in the model zoo routes through :func:`matmul` so that a weight
leaf may transparently be either a dense array or a
:class:`~repro.quant_runtime.qparams.QuantizedTensor`.

A quantized weight is dequantized and multiplied by XLA, on every backend.
The fused block-dequant Pallas kernel (`repro.kernels.fp8_matmul`) is wired
in behind ``USE_KERNELS`` for 2-D block-granularity fp8 weights only, and
that switch is off.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.quant_runtime.qparams import QuantizedTensor

# Routes 2-D block-granularity fp8 weights through the fused Pallas kernel.
# Off: serving runs the XLA dequantize + matmul below.
USE_KERNELS = False

# Calibration hook: when set to a list, every matmul appends
# (weight_shape, weight_fingerprint, per-in-channel |x| max) -- used by the
# SmoothQuant/AWQ methods with runtime.flags["unroll_layers"] so values are
# concrete.
RECORD: list | None = None


def weight_fingerprint(w) -> tuple:
    """Stable identity of a dense 2-D weight for calibration matching.

    Shape alone collides (wq/wo, wk/wv, gate/up all share shapes), so stats
    are keyed by sampled values instead: bf16->f32 casts are exact, so the
    fingerprint computed here during the forward equals the one computed
    from the parameter-tree leaf at quantization time.

    Contract: fingerprints only match when calibration and quantization run
    on the same backend/JAX build (the mean-abs reduction order must be
    identical).  A serialized ``calib=`` list from a different device class
    may miss every lookup — the equalize methods warn on the first miss.
    """
    w32 = jnp.asarray(w, jnp.float32)
    return (float(w32[0, 0]), float(w32[-1, -1]),
            float(jnp.mean(jnp.abs(w32))))


def resolve(w):
    """Return a dense array for a (possibly quantized) weight leaf."""
    if isinstance(w, QuantizedTensor):
        return w.dequantize()
    return w


@jax.named_scope("qlinear")
def matmul(x: jnp.ndarray, w, *, precision=None) -> jnp.ndarray:
    """x @ w with w possibly quantized. x: [..., in], w: [in, out]."""
    if RECORD is not None and not isinstance(x, jax.core.Tracer):
        w_res = resolve(w)
        RECORD.append((tuple(w_res.shape), weight_fingerprint(w_res),
                       jnp.max(jnp.abs(x.reshape(-1, x.shape[-1])), axis=0)))
    if isinstance(w, QuantizedTensor):
        # the fused kernel reads one scale per 128x128 block and dequantizes
        # q*scale only: channel/tensor scales and equalized tensors (extra
        # /eq_scale epilogue) take the XLA dequantize path
        if USE_KERNELS and w.ndim == 2 and w.granularity == "block" \
                and w.fmt.startswith("fp8") and w.eq_scale is None:
            from repro.kernels import fp8_matmul  # lazy: pallas import cost
            return fp8_matmul.ops.matmul_fp8(x, w)
        w = w.dequantize()
    return jnp.matmul(x, w.astype(x.dtype), precision=precision)


@jax.named_scope("qlinear")
def matmul_t(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x @ w.T`` with ``w`` possibly quantized — the tied-embedding LM
    head (``x [..., D] @ table[V, D].T``), which is the hot op of a
    speculative *draft* forward over storage-mode weights: every draft
    decode step projects to the full vocabulary.

    For tensor/channel-granularity :class:`QuantizedTensor` tables the
    scale factors move to the cheap side of the transpose instead of
    materializing the dequantized ``[V, D]`` table per step:

      ``x @ (q * s).T  ==  (x * s[0]) @ q.T``      (channel: s is [1, D])
      ``x @ (q * s).T  ==  s * (x @ q.T)``         (tensor: s is scalar)

    and a row-wise ``eq_scale`` divides the output columns.  Value-wise
    this matches ``x @ w.dequantize().T`` up to fp reassociation; block
    granularity (scales tile both axes) falls back to the dequantize path.
    """
    if not isinstance(w, QuantizedTensor):
        return jnp.matmul(x, w.T.astype(x.dtype))
    if w.ndim == 2 and w.granularity in ("tensor", "channel"):
        q = w.data.astype(jnp.float32)
        x32 = x.astype(jnp.float32)
        if w.granularity == "channel":      # scale [1, D] over columns of w
            out = jnp.matmul(x32 * w.scale.astype(jnp.float32)[0], q.T)
        else:                               # scalar scale
            out = jnp.matmul(x32, q.T) * jnp.float32(w.scale)
        if w.eq_scale is not None:          # per-row divisor of w
            out = out / w.eq_scale.astype(jnp.float32)
        return out.astype(x.dtype)
    return jnp.matmul(x, w.dequantize().T.astype(x.dtype))


@jax.named_scope("qlinear")
def take(embedding, ids):
    """Embedding lookup with optional quantized table.

    For a :class:`QuantizedTensor` table only the gathered rows are
    dequantized — dequantizing the whole ``[V, D]`` table per lookup made
    every decode step O(vocab) in storage mode.
    """
    if isinstance(embedding, QuantizedTensor) and embedding.ndim == 2:
        return _take_quantized(embedding, ids)
    return jnp.take(resolve(embedding), ids, axis=0)


def _take_quantized(w: QuantizedTensor, ids):
    """Row-gathered dequantization, matching ``w.dequantize()[ids]`` exactly
    (same fp32 q*scale math, same eq_scale epilogue)."""
    from repro.core.formats import get_format
    get_format(w.fmt)  # validate early; the math below is format-agnostic
    flat = jnp.asarray(ids, jnp.int32).reshape(-1)
    q = jnp.take(w.data, flat, axis=0).astype(jnp.float32)     # [N, O]
    if w.granularity == "block":
        # scale [I/bs, 1, O/bs, 1]: row r uses scale row r // bs; expand the
        # per-column-block scale to the (unpadded) O columns
        bs = w.block_size
        s = jnp.take(w.scale, flat // bs, axis=0)[:, 0, :, 0]  # [N, O/bs]
        s = jnp.repeat(s, bs, axis=1)[:, : q.shape[-1]]
        rows = q * s
    else:  # tensor: scalar; channel: [1, O] — both broadcast over rows
        rows = q * w.scale
    if w.eq_scale is not None:
        rows = rows / jnp.take(w.eq_scale, flat, axis=0)[:, None]
    out = rows.astype(jnp.dtype(w.out_dtype))
    return out.reshape(*jnp.shape(ids), out.shape[-1])
