"""Pure-jnp oracle for the paged attention kernel: the block-table gather
of every slot's whole table, the fresh rows laid over it, and a plain
softmax under the length mask."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_attention_ref(q, k_new, v_new, pk, pv, layer, tbl, start, *,
                        window: int = 0, softcap: float = 0.0):
    """q [B,C,H,hd]; k_new/v_new [B,C,Kv,hd] in the pool dtype, the rows of
    positions ``start .. start + C - 1``; pools [n, NB+1, bs, Kv, hd];
    tbl [B, MB]; start [B].  Row ``j`` attends the pool rows ``< start``
    and the fresh rows ``<= j`` (and, with ``window``, only the last
    ``window`` positions).  Returns [B, C, H, hd] in q's dtype."""
    from repro.engine.paged import gather_blocks
    B, C, H, hd = q.shape
    Kv = k_new.shape[2]
    G = H // Kv
    cap = tbl.shape[1] * pk.shape[2]
    qg = q.reshape(B, C, Kv, G, hd)
    pos = jnp.broadcast_to(jnp.arange(cap)[None], (B, cap))
    gk = gather_blocks(pk, tbl, layer)
    gv = gather_blocks(pv, tbl, layer)
    rel = pos - start[:, None]
    fresh = (rel >= 0) & (rel < C)
    idx = jnp.clip(rel, 0, C - 1)[..., None, None]
    fm = fresh[..., None, None]
    kbuf = jnp.where(fm, jnp.take_along_axis(k_new, idx, axis=1), gk)
    vbuf = jnp.where(fm, jnp.take_along_axis(v_new, idx, axis=1), gv)
    if kbuf.dtype.itemsize == 1:                            # fp8 cache
        kbuf = kbuf.astype(jnp.bfloat16)
        vbuf = vbuf.astype(jnp.bfloat16)
    s = jnp.einsum("bqkgh,bckh->bkgqc", qg, kbuf,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    qpos = start[:, None] + jnp.arange(C)[None]             # [B, C]
    mask = pos[:, None, :] < (qpos + 1)[..., None]          # [B, C, cap]
    if window > 0:                                          # non-ring SWA
        mask = mask & (pos[:, None, :] > (qpos[..., None] - window))
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqc,bckh->bqkgh", p.astype(vbuf.dtype), vbuf,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, C, H, hd).astype(q.dtype)
