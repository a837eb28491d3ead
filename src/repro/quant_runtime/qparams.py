"""QuantizedTensor: a pytree node holding low-precision weight storage.

Model code calls ``repro.quant_runtime.qlinear.matmul(x, w)`` for every
linear; when ``w`` is a ``QuantizedTensor`` the weight is dequantized on the
fly and XLA runs the matmul.  Because the
node is a registered pytree, quantized parameter trees flow through
``jax.jit``, ``jax.eval_shape``, shardings and checkpointing unchanged.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.formats import get_format
from repro.core.granularity import dequantize_stored


@dataclass
class QuantizedTensor:
    data: jnp.ndarray            # storage repr (fp8/int8), same layout as W
    scale: jnp.ndarray           # broadcastable scales (see granularity.py)
    fmt: str = "fp8_e4m3"        # static
    granularity: str = "block"   # static
    block_size: int = 128        # static
    out_dtype: str = "bfloat16"  # static: dequantization target dtype
    eq_scale: jnp.ndarray | None = None  # per-in-channel equalization s:
                                         # data stores Q(W*s), dequant /= s

    @property
    def shape(self):
        return self.data.shape

    @property
    def ndim(self):
        return self.data.ndim

    def dequantize(self) -> jnp.ndarray:
        fmt = get_format(self.fmt)
        dt = jnp.dtype(self.out_dtype)
        if self.eq_scale is None:
            fn = lambda d, s: dequantize_stored(d, s, self.granularity, fmt,
                                                self.block_size, dt)
            args = (self.data, self.scale)
        else:
            def fn(d, s, e):
                w = dequantize_stored(d, s, self.granularity, fmt,
                                      self.block_size, jnp.float32)
                return (w / e[:, None]).astype(dt)
            args = (self.data, self.scale, self.eq_scale)
        # stacked layers: vmap the 2-D dequant over leading axes
        for _ in range(self.data.ndim - 2):
            fn = jax.vmap(fn)
        return fn(*args)

    def nbytes(self) -> int:
        fmt = get_format(self.fmt)
        n = self.data.size * fmt.bits // 8 + self.scale.size * 4
        if self.eq_scale is not None:
            n += self.eq_scale.size * 4
        return n


jax.tree_util.register_dataclass(
    QuantizedTensor,
    data_fields=["data", "scale", "eq_scale"],
    meta_fields=["fmt", "granularity", "block_size", "out_dtype"],
)
