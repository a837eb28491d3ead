"""Low-precision number formats: quantize / dequantize primitives.

The paper instantiates DAQ with FP8 (E4M3).  The DAQ objective is format
agnostic (paper Sec. 2.2), so we also provide FP8 E5M2 and symmetric INT8 /
INT4 — the INT formats are where delta corruption is most visible and are
used by the beyond-paper studies.

All functions are jit-safe, shape-polymorphic and vmap-able.  ``quantize``
maps a float tensor to its low-precision storage representation under a
scale; ``dequantize`` maps it back.  ``qdq = dequantize(quantize(.))`` is the
quantize-dequantize operator :math:`Q_s(W)` from paper Eq. 4.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Format:
    name: str
    qmax: float                  # largest representable magnitude
    storage_dtype: jnp.dtype     # dtype of the stored representation
    is_float: bool
    bits: int


FP8_E4M3 = Format("fp8_e4m3", 448.0, jnp.float8_e4m3fn, True, 8)
FP8_E5M2 = Format("fp8_e5m2", 57344.0, jnp.float8_e5m2, True, 8)
INT8 = Format("int8", 127.0, jnp.int8, False, 8)
# INT4 stored widened in int8 (packing is a storage detail, not a numerics one)
INT4 = Format("int4", 7.0, jnp.int8, False, 4)

FORMATS: dict[str, Format] = {f.name: f for f in (FP8_E4M3, FP8_E5M2, INT8, INT4)}


def get_format(name: str) -> Format:
    if name not in FORMATS:
        raise KeyError(f"unknown format {name!r}; available: {sorted(FORMATS)}")
    return FORMATS[name]


def quantize(w: jnp.ndarray, scale: jnp.ndarray, fmt: Format) -> jnp.ndarray:
    """Map ``w`` to low-precision storage under ``scale`` (broadcastable).

    FP8 casts saturate (jax/ml_dtypes overflow to NaN, so we clip first);
    INT formats round-to-nearest-even then clip.
    """
    scaled = (w / scale).astype(jnp.float32)
    if fmt.is_float:
        clipped = jnp.clip(scaled, -fmt.qmax, fmt.qmax)
        return clipped.astype(fmt.storage_dtype)
    rounded = jnp.round(scaled)  # round-half-to-even, matches hardware RTNE
    clipped = jnp.clip(rounded, -fmt.qmax, fmt.qmax)
    return clipped.astype(fmt.storage_dtype)


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, fmt: Format,
               out_dtype: jnp.dtype = jnp.float32) -> jnp.ndarray:
    """Map low-precision storage back to the floating-point domain."""
    return (q.astype(jnp.float32) * scale).astype(out_dtype)


def qdq(w: jnp.ndarray, scale: jnp.ndarray, fmt: Format) -> jnp.ndarray:
    """Quantize-dequantize operator :math:`Q_s(W)` (paper Eq. 4), fp32 out.

    The barrier makes XLA materialise the low-precision codes.  Without it
    the TPU compiler fuses ``convert(convert(x, fp8), f32)`` into one
    kernel and, allowed excess precision, skips the rounding: Q_s becomes
    the identity and every DAQ candidate scores as lossless."""
    q = jax.lax.optimization_barrier(quantize(w, scale, fmt))
    return dequantize(q, scale, fmt)
