"""Jitted wrapper for the fp8 block-quantize kernel (pads ragged edges)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.granularity import pad_to_blocks
from repro.kernels.fp8_quant.kernel import quantize_fp8_pallas


@partial(jax.jit, static_argnames=("block",))
def quantize_fp8(w: jnp.ndarray, alpha: float | jnp.ndarray = 1.0, *,
                 block: int = 128):
    """w [I, O] -> (q [I, O] fp8 (unpadded layout), scales [ceil(I/b), ceil(O/b)])."""
    I, O = w.shape
    wp, _ = pad_to_blocks(w.astype(jnp.float32), block)
    a = jnp.asarray(alpha, jnp.float32).reshape(1)
    q, s = quantize_fp8_pallas(wp, a, block=block)
    return q[:I, :O], s
