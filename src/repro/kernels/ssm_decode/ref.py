"""Pure-jnp oracle for the Mamba-2 decode-step kernel: one step of the
exact recurrence ``h = a h + dt x B^T; y = C h`` (``a = exp(A dt)``), and
its form on the stacked state (slice the layer out, step, keep the slots
outside ``keep``, write the layer back)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def step(h, x, B, C, dt, A):
    """h [B,nh,P,N] float32; x [B,nh,P] float32; B/C [B,N]; dt [B,nh]
    (post-softplus); A [nh] (negative).  Returns (new h, y [B,nh,P]) with
    ``y = C h_new`` (no skip term)."""
    a = jnp.exp(A[None] * dt)                              # [B,nh]
    dBx = jnp.einsum("bhp,bn,bh->bhpn", x, B.astype(jnp.float32), dt)
    h = h * a[..., None, None] + dBx
    y = jnp.einsum("bn,bhpn->bhp", C.astype(jnp.float32), h)
    return h, y


def ssm_decode_ref(h, layer, keep, x, B, C, dt, A):
    """:func:`step` on the stacked state h [n,B,nh,P,N] at ``layer``; the
    slots outside ``keep`` [B] (all when None) keep their state, and the
    new state is stored in h's dtype.  Returns (stacked h, y [B,nh,P])."""
    old = jax.lax.dynamic_index_in_dim(h, layer, 0, False)
    new, y = step(old, x, B, C, dt, A)
    new = new.astype(h.dtype)
    if keep is not None:
        new = jnp.where(keep[:, None, None, None], new, old)
    return jax.lax.dynamic_update_index_in_dim(h, new, layer, 0), y
