"""Jitted wrapper for the fused fp8 dequant-matmul.

``matmul_fp8(x, qt)`` consumes a QuantizedTensor (block granularity) and
handles: leading batch dims on x, padding to tile multiples, and the epilogue
cast to x's dtype.  Serving does not route through it; ``qlinear`` runs an
XLA dequantize + matmul.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fp8_matmul.kernel import matmul_fp8_pallas


@partial(jax.jit, static_argnames=("block",))
def matmul_fp8_2d(x, wq, scales, *, block: int = 128):
    M, K = x.shape
    N = wq.shape[1]
    pm = (-M) % min(128, max(M, 8))
    pk = (-K) % block
    pn = (-N) % block
    if pk or pn:
        raise ValueError("fp8 weights must be padded to the quant block")
    xp = jnp.pad(x, ((0, pm), (0, 0))) if pm else x
    out = matmul_fp8_pallas(xp, wq, scales, bm=min(128, xp.shape[0]),
                            block=block)
    return out[:M]


def matmul_fp8(x: jnp.ndarray, qt) -> jnp.ndarray:
    """x [..., K] @ QuantizedTensor(block) -> [..., N] in x.dtype."""
    if qt.granularity != "block":
        raise ValueError(f"the fp8 kernel reads block scales, got "
                         f"{qt.granularity!r} granularity")
    scales = qt.scale
    if scales.ndim == 4:      # [K/bs, 1, N/bs, 1] broadcast layout
        scales = scales[:, 0, :, 0]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = matmul_fp8_2d(x2, qt.data, scales, block=qt.block_size)
    return out.reshape(*lead, out.shape[-1]).astype(x.dtype)
