"""Paged attention through the block table: the Pallas kernel where the
program is lowered for a TPU and the pools' layout suits it, the gather
(``ref``) everywhere else.

The choice is made from what the program can observe, never from a flag:
the lowering platform (``lax.platform_dependent``), the pools' dtype and
shape, the sliding window, and whether the pools are sharded over a mesh
(an unpartitioned kernel there would gather the pools onto every device).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref

CHUNK_BYTES = 128 << 10    # one chunk's K rows in VMEM (V as many)
LANES = 128
# Lowering platforms that run the kernel (tests add "cpu" to run it, in
# interpret mode, inside a whole engine).
KERNEL_PLATFORMS = ("tpu",)


def _per_platform(kernel, ref):
    return jax.lax.platform_dependent(
        **{p: kernel for p in KERNEL_PLATFORMS}, default=ref)


def blocks_per_chunk(bs: int, Kv: int, hd: int, MB: int,
                     itemsize: int = 2) -> int:
    """Blocks a chunk: about ``CHUNK_BYTES`` of K, at least one lane width
    of positions, and no more than the table holds (rounded up to a lane
    width of positions)."""
    step = max(1, LANES // bs)                 # blocks a lane width
    nb = max(step, CHUNK_BYTES // (Kv * hd * itemsize) // bs)
    nb -= nb % step
    return min(nb, -(-MB // step) * step)


def kernel_fits(pk, tbl, window: int = 0) -> bool:
    """Whether the kernel serves these pools: 16-bit floats with the
    ``[Kv, hd]`` rows of a token dense in the TPU's tiling (Kv / 2 words a
    token: 1, 2, 4 or a multiple of 8), lane-wide heads and chunks, no
    sliding window, and no mesh over the pools."""
    n, _, bs, Kv, hd = pk.shape
    words = Kv // 2
    mesh = jax.typeof(pk).sharding.mesh
    return (pk.dtype == jnp.bfloat16 and not window
            and Kv % 2 == 0 and (words in (1, 2, 4) or words % 8 == 0)
            and hd % LANES == 0
            and blocks_per_chunk(bs, Kv, hd, tbl.shape[1]) * bs % LANES == 0
            and mesh.size <= 1)


@jax.named_scope("paged_attn")
def paged_attention_kernel(q, k_new, v_new, pk, pv, layer, tbl, start, *,
                           softcap: float = 0.0):
    """The kernel on the caller's shapes: q [B,C,H,hd], k_new/v_new
    [B,C,Kv,hd] (pool dtype), pools [n, NB+1, bs, Kv, hd], tbl [B, MB],
    start [B] -> [B, C, H, hd] in q's dtype."""
    B, C, H, hd = q.shape
    Kv = k_new.shape[2]
    Cr = -(-C * Kv // 8) * 8                   # a float32 tile of rows

    def fresh(x):                              # [B, Cr, hd] float32
        x = x.astype(jnp.float32).reshape(B, C * Kv, hd)
        return jnp.pad(x, ((0, 0), (0, Cr - C * Kv), (0, 0)))

    nb = blocks_per_chunk(pk.shape[2], Kv, hd, tbl.shape[1])
    out = paged_attention_pallas(
        q.astype(pk.dtype), fresh(k_new), fresh(v_new), pk, pv,
        jnp.asarray(layer, jnp.int32).reshape(1), tbl.astype(jnp.int32),
        start.astype(jnp.int32), blocks_per_chunk=nb, scale=hd ** -0.5,
        softcap=softcap)
    return out.astype(q.dtype)


def paged_attention(q, k_new, v_new, pk, pv, layer, tbl, start, *,
                    window: int = 0, softcap: float = 0.0, ref=None):
    """Row ``j`` of q [B,C,H,hd] attends the pool rows ``< start`` of its
    slot and the fresh rows ``k_new``/``v_new`` [B,C,Kv,hd] ``<= j``.  The
    kernel on a TPU where :func:`kernel_fits`; else ``ref()`` (default:
    :func:`ref.paged_attention_ref`)."""
    if ref is None:
        def ref():
            return paged_attention_ref(q, k_new, v_new, pk, pv, layer, tbl,
                                       start, window=window, softcap=softcap)
    if not kernel_fits(pk, tbl, window):
        return ref()
    return _per_platform(
        lambda: paged_attention_kernel(q, k_new, v_new, pk, pv, layer, tbl,
                                       start, softcap=softcap), ref)


def rows_read(start, pk, tbl, *, window: int = 0):
    """Pool rows one layer of :func:`paged_attention` reads, over all slots
    (int32): whole blocks up to each slot's ``start`` on the kernel path,
    the whole table (``MB * bs`` a slot) on the gather path."""
    B, MB = tbl.shape
    bs = pk.shape[2]
    full = jnp.int32(B * MB * bs)
    if not kernel_fits(pk, tbl, window):
        return full
    n = (jnp.minimum(start, MB * bs) + bs - 1) // bs
    return _per_platform(lambda: jnp.sum(n * bs, dtype=jnp.int32),
                         lambda: full)
