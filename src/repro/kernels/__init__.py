"""Pallas TPU kernels, each a kernel / ops / ref triad:

  scale_search    -- fused DAQ candidate sweep (the paper's Alg. 1 hot-spot)
  fp8_matmul      -- fused block-dequant matmul
  fp8_quant       -- one-pass block absmax + E4M3 cast
  paged_attention -- attention against the paged K/V pools through the
                     block table, up to each slot's length
  ssm_decode      -- Mamba-2's decode step on the stacked per-slot state,
                     in place: one read and one write of a layer's state

Serving runs ``paged_attention`` and ``ssm_decode`` on a TPU: the engine's
paged decode and speculative-verify attention
(``models.attention.paged_decode_attention``, ``paged_verify_attention``)
call the first where the pools' layout suits it, and Mamba-2's paged
decode step (``models.ssm.mamba_decode`` with a layer) the second where
the state's does.  It does not call the other three:
``quant_runtime.qlinear`` runs quantized weights as an XLA dequantize +
matmul.  ``tests/test_tpu_compile.py``
compiles each kernel for a v5e chip at glm4-9b widths.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kw):
    """``pl.pallas_call`` that runs in Pallas interpret mode on the CPU
    backend (the test suite) and compiled everywhere else.

    The choice is made per lowering platform (``lax.platform_dependent``),
    so a program lowered for a TPU never carries the interpreter, even when
    the process's default backend is the CPU."""
    compiled = pl.pallas_call(kernel, **kw)
    interpreted = pl.pallas_call(kernel, interpret=True, **kw)
    return lambda *args: jax.lax.platform_dependent(
        *args, cpu=interpreted, default=compiled)
