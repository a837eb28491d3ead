"""Mamba-2's decode step on the stacked state, in place: one pass that
reads each slot's ``[nh, P, N]`` state of one layer once and writes it
back.

The state ``h`` ``[n, B, nh, P, N]`` (every layer of the stack) is aliased
input to output.  The grid runs over (slot, head block); each program
loads the ``[hb, P, N]`` tile of ``h[layer, b]``, forms the new state
``a h + (dt x) B^T``, reduces ``y = C h_new`` from it, and stores the tile
where it came from.  A slot outside ``keep`` stores the tile it loaded, so
its state stays bit for bit.  No other layer or slot is touched: the
pipeline reads and writes only the tiles of ``h[layer]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call


def _kernel(layer_ref, keep_ref,                           # scalar prefetch
            x_ref, b_ref, c_ref, dt_ref, a_ref, h_ref,      # inputs
            h_out, y_ref):                                 # outputs
    h = h_ref[...]                                 # [hb, P, N]
    xdt = x_ref[...] * dt_ref[...]                 # [hb, P]
    new = h * a_ref[...][:, :, None] + xdt[:, :, None] * b_ref[...][None]
    h_out[...] = jnp.where(keep_ref[pl.program_id(0)] != 0, new, h)
    y_ref[...] = jnp.sum(new * c_ref[...][None], axis=-1)


def ssm_decode_pallas(h, layer, keep, x, B, C, dt, a, *,
                      heads_per_block: int):
    """h [n,B,nh,P,N] float32 (aliased to the first output); ``layer``
    int32 [1]; keep int32 [B]; x [B,nh,P], B/C [B,N], dt/a [B,nh] float32.
    Returns (h, y [B,nh,P] float32)."""
    n, Bsz, nh, P, N = h.shape
    hb = heads_per_block
    tile = pl.BlockSpec((None, None, hb, P, N),
                        lambda b, j, layer, keep: (layer[0], b, j, 0, 0))
    heads = pl.BlockSpec((None, hb, P), lambda b, j, *_: (b, j, 0))
    row = pl.BlockSpec((None, 1, N), lambda b, j, *_: (b, 0, 0))
    col = pl.BlockSpec((None, hb, 1), lambda b, j, *_: (b, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bsz, nh // hb),
        in_specs=[heads, row, row, col, col, tile],
        out_specs=[tile, heads],
    )
    return pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(h.shape, h.dtype),
                   jax.ShapeDtypeStruct((Bsz, nh, P), jnp.float32)],
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="ssm_decode",
    )(layer, keep, x, B.reshape(Bsz, 1, N), C.reshape(Bsz, 1, N),
      dt[..., None], a[..., None], h)
