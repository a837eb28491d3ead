"""Fused block-dequant fp8 matmul kernel.

y = x @ dequant(w_q, scales):  x [M, K], w_q [K, N] fp8-E4M3 with one fp32
scale per 128x128 block.  The weight tile is widened to x's dtype in VMEM on
its way into the MXU, so weight HBM traffic is 1 byte/elem instead of 2
(bf16) — the bound at decode, where a matmul is weight-bandwidth-limited.

Tiling: grid (M/bm, N/bn, K/bk) with bk = bn = 128 (the quant block edge),
so each weight tile has exactly one scale, applied to the tile's fp32
partial product.  The whole scale grid sits in SMEM (1 MiB on v5e holds the
scales of any K*N up to ~4e9).  fp32 accumulation happens in the output
block, which is revisited across the innermost K grid axis (the standard
Pallas revisiting pattern); the cast to x's dtype is the wrapper's epilogue.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call


def _matmul_kernel(x_ref, wq_ref, scale_ref, o_ref):
    n, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    part = jnp.dot(x, wq_ref[...].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    o_ref[...] += part * scale_ref[k, n]


def matmul_fp8_pallas(x: jnp.ndarray, wq: jnp.ndarray, scales: jnp.ndarray,
                      *, bm: int = 128, block: int = 128) -> jnp.ndarray:
    """x [M, K]; wq [K, N] fp8; scales [K/block, N/block] fp32.
    Returns fp32 [M, N] (caller casts)."""
    M, K = x.shape
    N = wq.shape[1]
    bm = min(bm, M)
    n_m, n_n, n_k = M // bm, N // block, K // block
    return pallas_call(
        _matmul_kernel,
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((bm, block), lambda m, n, k: (m, k)),
            pl.BlockSpec((block, block), lambda m, n, k: (k, n)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, block), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
    )(x, wq, scales)
