"""Paged attention against the stacked K/V pools, read through the block
table up to each slot's length.

The pools ``[n, NB+1, bs, Kv, hd]`` stay in HBM (``memory_space=ANY``) and
are never written or sliced by layer: for slot ``b`` the kernel DMAs the
blocks ``tbl[b, 0 .. ceil(start[b]/bs) - 1]`` of ``pool[layer]``, ``nb``
blocks a chunk, double-buffered, and the first chunk of slot ``b + 1`` is
already in flight while slot ``b``'s last chunk is computed.  No row at or
past ``start[b]`` is read from the pool: the ``C`` fresh rows of positions
``start .. start + C - 1`` come in as an input and are laid over the
chunk buffer at their positions.  Query row ``j`` attends positions
``< min(start + j + 1, cap)`` (``cap = MB * bs``).

A block lands in VMEM in the pool's stored layout, ``[bs, Kv, hd]``, so a
chunk is ``[T * Kv, hd]`` with row ``t * Kv + h`` the position ``t`` of KV
head ``h``.  All query heads score the whole chunk in one dot, and a
column counts for a query head only where its KV head is the query head's
(``pos``, a constant input: the column's position, or a large number where
the heads differ).  Each query row runs its own online softmax over the
chunks in float32: every dot has the same shape whatever ``C`` is and a
row's chunks start at the same positions, so row ``j`` of a ``C``-row call
equals the one-row call at position ``start + j`` bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call

NEG = -1e30
FAR = 1 << 30               # ``pos`` of a column of another KV head
TILE = 16                   # rows of a 16-bit VMEM tile


def _kernel(layer_ref, tbl_ref, start_ref,                 # scalar prefetch
            q_ref, kn_ref, vn_ref, pos_ref, pk_hbm, pv_hbm,  # inputs
            o_ref,                                         # output
            kbuf, vbuf, sems, nxt_ref,                     # scratch
            *, bs, nb, Kv, C, MB, scale, softcap):
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    layer = layer_ref[0]
    trash = pk_hbm.shape[1] - 1
    cap = MB * bs
    T = nb * bs                                   # positions a chunk
    N = T * Kv                                    # rows a chunk
    hd = q_ref.shape[3]
    Wn = kn_ref.shape[1] + TILE                   # rows the fresh ones touch

    def pool_blocks(s):                           # blocks slot s reads
        return (jnp.minimum(start_ref[s], cap) + bs - 1) // bs

    def copies(s, c, buf):
        """(block index, K copy, V copy) of chunk ``c`` of slot ``s``."""
        out = []
        for i in range(nb):
            idx = c * nb + i
            blk = tbl_ref[s * MB + jnp.minimum(idx, MB - 1)]
            blk = jnp.where(blk < 0, trash, blk)
            rows = pl.ds(i * bs * Kv, bs * Kv)
            out.append((idx,
                        pltpu.make_async_copy(
                            pk_hbm.at[layer, blk].reshape(bs * Kv, hd),
                            kbuf.at[buf, rows], sems.at[0, buf]),
                        pltpu.make_async_copy(
                            pv_hbm.at[layer, blk].reshape(bs * Kv, hd),
                            vbuf.at[buf, rows], sems.at[1, buf])))
        return out

    def fetch(s, c, buf):
        n = pool_blocks(s)
        cps = copies(s, c, buf)

        @pl.when((c + 1) * nb <= n)
        def _():                                  # a whole chunk
            for _, ck, cv in cps:
                ck.start()
                cv.start()

        @pl.when((c + 1) * nb > n)
        def _():
            for idx, ck, cv in cps:
                @pl.when(idx < n)
                def _():
                    ck.start()
                    cv.start()

    def land(s, c, buf):
        n = pool_blocks(s)

        @pl.when((c + 1) * nb <= n)
        def _():                                  # a DMA semaphore counts
            for ref, sem in ((kbuf, sems.at[0, buf]),   # bytes: one wait
                             (vbuf, sems.at[1, buf])):  # for the chunk
                pltpu.make_async_copy(ref.at[buf], ref.at[buf], sem).wait()

        @pl.when((c + 1) * nb > n)
        def _():
            for idx, ck, cv in copies(s, c, buf):
                @pl.when(idx < n)
                def _():
                    ck.wait()
                    cv.wait()

    @pl.when(b == 0)
    def _():
        # rows no DMA fills hold zeros or earlier pool rows, never NaN
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        nxt_ref[0] = 0
        fetch(0, 0, 0)

    start = start_ref[b]
    end = jnp.minimum(start + C, cap)             # positions that exist
    n_chunks = jnp.maximum((end + T - 1) // T, 1)
    first = nxt_ref[0]
    n_fresh = jnp.maximum(end - start, 0) * Kv    # fresh rows that exist
    row = jax.lax.broadcasted_iota(jnp.int32, (Wn, hd), 0)

    def chunk(c, carry):
        buf = (first + c) % 2
        last = c + 1 == n_chunks

        @pl.when(jnp.logical_not(last))
        def _():
            fetch(b, c + 1, 1 - buf)

        @pl.when(last & (b + 1 < nslots))
        def _():
            fetch(b + 1, 0, 1 - buf)

        land(b, c, buf)
        base = c * T
        k_ref, v_ref = kbuf.at[buf], vbuf.at[buf]
        off = (start - base) * Kv                 # chunk row of position start

        @pl.when((off < N) & (off + n_fresh > 0))
        def _():                                  # lay the fresh rows over
            w0 = pl.multiple_of(
                jnp.clip(off // TILE * TILE, 0, N - Wn), TILE)
            at = row + (w0 - off)                 # fresh row held, if any
            for ref, new in ((k_ref, kn_ref), (v_ref, vn_ref)):
                win = ref[pl.ds(w0, Wn), :].astype(jnp.float32)
                for i in range(new.shape[1]):
                    win = jnp.where((at == i) & (i < n_fresh),
                                    new[0, i:i + 1, :], win)
                ref[pl.ds(w0, Wn), :] = win.astype(ref.dtype)

        k = k_ref[...]
        v = v_ref[...]
        pos = pos_ref[...]
        out = []
        for j in range(C):
            m, l, acc = carry[j]
            s = jax.lax.dot_general(
                q_ref[0, j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(pos < jnp.minimum(start + j + 1, cap) - base, s,
                          NEG)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new)
            l = alpha * l + e.sum(axis=1, keepdims=True)
            acc = alpha * acc + jax.lax.dot_general(
                e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append((m_new, l, acc))
        return tuple(out)

    H = q_ref.shape[2]
    init = tuple((jnp.full((H, 1), NEG, jnp.float32),
                  jnp.zeros((H, 1), jnp.float32),
                  jnp.zeros((H, hd), jnp.float32)) for _ in range(C))
    res = jax.lax.fori_loop(0, n_chunks, chunk, init)
    nxt_ref[0] = (first + n_chunks) % 2
    for j in range(C):
        _, l, acc = res[j]
        o_ref[0, j] = acc / l


def chunk_positions(H: int, Kv: int, T: int) -> np.ndarray:
    """``pos`` [H, T * Kv] int32: for query head ``r`` and chunk row ``c``
    (position ``c // Kv`` of KV head ``c % Kv``), the position where the
    KV head is query head ``r``'s, else :data:`FAR`."""
    G = H // Kv
    c = np.arange(T * Kv)[None]
    same = (c % Kv) == (np.arange(H)[:, None] // G)
    return np.where(same, c // Kv, FAR).astype(np.int32)


def paged_attention_pallas(q, kn, vn, pk, pv, layer, tbl, start, *,
                           blocks_per_chunk: int, scale: float,
                           softcap: float = 0.0):
    """q [B, C, H, hd] 16-bit; kn/vn [B, Cr, hd] float32, the fresh rows
    (row ``j * Kv + h``: position ``start + j`` of KV head ``h``; ``Cr``
    >= ``C * Kv``, a multiple of 8); pools [n, NB+1, bs, Kv, hd] 16-bit;
    ``layer`` int32 [1]; tbl [B, MB] int32 (-1: unallocated); start [B]
    int32.  Returns [B, C, H, hd] float32."""
    B, C, H, hd = q.shape
    _, _, bs, Kv, _ = pk.shape
    MB = tbl.shape[1]
    nb = blocks_per_chunk
    N = nb * bs * Kv
    Cr = kn.shape[1]
    assert Cr + TILE <= N, (Cr, N)            # the fresh rows' window fits
    kernel = functools.partial(_kernel, bs=bs, nb=nb, Kv=Kv, C=C, MB=MB,
                               scale=scale, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, C, H, hd), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, Cr, hd), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, Cr, hd), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((H, N), lambda b, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, C, H, hd), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, N, hd), pk.dtype),
            pltpu.VMEM((2, N, hd), pv.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    pos = jnp.asarray(chunk_positions(H, Kv, nb * bs))
    return pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, H, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention",
    )(layer, tbl.reshape(-1), start, q, kn, vn, pos, pk, pv)
