"""Registered data-free methods: DAQ (paper Alg. 1) and the AbsMax baseline.

The per-leaf search lives in :mod:`repro.core.search`; stacked-layer leaves
``[L, I, O]`` run the per-matrix search in a ``lax.map`` over the leading
axes — one alpha per layer, exactly Alg. 1's per-layer loop, with one
layer's working set live at a time.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax

from repro.configs.base import QuantConfig
from repro.core.search import SearchResult, search_scale
from repro.quantize.api import LeafContext, Quantizer
from repro.quantize.registry import register


@register("daq")
class DAQQuantizer(Quantizer):
    """Delta-aware coarse-to-fine scale search; objective = ``qcfg.metric``.

    Honors ``qcfg.per_block_alpha`` / ``qcfg.use_fused_kernel`` exactly like
    the per-leaf search always has (``search_scale`` dispatches internally).
    """

    def prepare(self, ctx: LeafContext) -> SearchResult:
        return search_stacked(ctx.w_post, ctx.w_base, ctx.qcfg)


@partial(jax.jit, static_argnames=("qcfg",))
def search_stacked(w_post, w_base, qcfg: QuantConfig) -> SearchResult:
    """:func:`search_scale` over every 2-D matrix of a ``[..., I, O]`` leaf."""
    fn = lambda p, b: search_scale(p, b, qcfg)
    for _ in range(w_post.ndim - 2):
        fn = lambda p, b, f=fn: jax.lax.map(lambda pb: f(*pb), (p, b))
    return fn(w_post, w_base)


@register("daq-per-block")
class DAQPerBlockQuantizer(DAQQuantizer):
    """Beyond-paper variant: independent alpha per block / channel."""

    def resolve_config(self, qcfg: QuantConfig) -> QuantConfig:
        return dataclasses.replace(qcfg, per_block_alpha=True,
                                   use_fused_kernel=False)


@register("absmax")
class AbsMaxQuantizer(DAQQuantizer):
    """AbsMax baseline = Alg. 1 with an empty search (alpha fixed at 1).

    Collapsing the search must clear *every* search knob, not just the grid:
    ``per_block_alpha`` and ``use_fused_kernel`` are reset so a caller with a
    fused-sweep or per-block config still gets a plain AbsMax baseline.
    """

    def resolve_config(self, qcfg: QuantConfig) -> QuantConfig:
        return dataclasses.replace(qcfg, n_coarse=1, n_fine=1,
                                   alpha_min=1.0, alpha_max=1.0,
                                   per_block_alpha=False,
                                   use_fused_kernel=False)
