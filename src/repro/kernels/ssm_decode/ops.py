"""Mamba-2's decode step on the stacked state: the Pallas kernel where the
program is lowered for a TPU and the state's layout suits it, the XLA step
(``ref``) everywhere else.

The choice is made from what the program can observe, never from a flag:
the lowering platform (``lax.platform_dependent``), the state's dtype and
shape, and whether it is sharded over a mesh (an unpartitioned kernel
there would gather the state onto every device).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssm_decode.kernel import ssm_decode_pallas
from repro.kernels.ssm_decode.ref import ssm_decode_ref

TILE_BYTES = 512 << 10     # one program's [hb, P, N] float32 state tile
SUBLANES, LANES = 8, 128
# Lowering platforms that run the kernel (tests add "cpu" to run it, in
# interpret mode, inside a whole engine).
KERNEL_PLATFORMS = ("tpu",)


def _per_platform(kernel, ref):
    return jax.lax.platform_dependent(
        **{p: kernel for p in KERNEL_PLATFORMS}, default=ref)


def heads_per_block(nh: int, P: int, N: int) -> int:
    """Heads a program: the most whose float32 tile fits ``TILE_BYTES``,
    among the divisors of ``nh`` that are a multiple of 8 or ``nh`` itself
    (a block's head axis is tiled by sublanes); 0 if none fits."""
    return max((d for d in range(1, nh + 1)
                if nh % d == 0 and (d % SUBLANES == 0 or d == nh)
                and d * P * N * 4 <= TILE_BYTES), default=0)


def kernel_fits(h) -> bool:
    """Whether the kernel serves this stacked state ``[n, B, nh, P, N]``:
    float32 with each head's ``[P, N]`` tile dense in the TPU's tiling
    (``P`` a multiple of 8, ``N`` of 128), a head block that fits
    ``TILE_BYTES``, and no mesh over the state."""
    if h.ndim != 5:
        return False
    _, _, nh, P, N = h.shape
    mesh = jax.typeof(h).sharding.mesh
    return (h.dtype == jnp.float32 and P % SUBLANES == 0
            and N % LANES == 0
            and heads_per_block(nh, P, N) > 0
            and mesh.size <= 1)


@jax.named_scope("mamba_state")
def ssm_decode_kernel(h, layer, keep, x, B, C, dt, A):
    """The kernel on the caller's shapes (see :func:`ssm_decode`)."""
    Bsz, nh, P, N = h.shape[1:]
    keep = (jnp.ones((Bsz,), jnp.int32) if keep is None
            else keep.astype(jnp.int32))
    a = jnp.exp(A[None] * dt)
    return ssm_decode_pallas(
        h, jnp.asarray(layer, jnp.int32).reshape(1), keep,
        x.astype(jnp.float32), B.astype(jnp.float32),
        C.astype(jnp.float32), dt.astype(jnp.float32), a,
        heads_per_block=heads_per_block(nh, P, N))


def ssm_decode(h, layer, keep, x, B, C, dt, A):
    """One decode step of every slot's state at ``layer`` of the stacked
    state h [n,B,nh,P,N], in place: ``h = a h + dt x B^T`` (``a = exp(A
    dt)``), ``y = C h``.  x [B,nh,P]; B/C [B,N]; dt [B,nh] (post-softplus);
    A [nh]; keep [B] bool (None: every slot): the other slots keep their
    state.  Returns (stacked h, y [B,nh,P] float32).  The kernel on a TPU
    where :func:`kernel_fits`; else :func:`ref.ssm_decode_ref`."""
    def ref():
        return ssm_decode_ref(h, layer, keep, x, B, C, dt, A)
    if not kernel_fits(h):
        return ref()
    return _per_platform(
        lambda: ssm_decode_kernel(h, layer, keep, x, B, C, dt, A), ref)


def states_updated(h, keep):
    """(slot, layer) states the kernel updates in one decode step of the
    stacked state h [n,B,...] (int32): ``n`` times the slots in ``keep``
    on the kernel path, 0 on the XLA path."""
    if not kernel_fits(h):
        return jnp.int32(0)
    n, Bsz = h.shape[:2]
    k = jnp.int32(Bsz) if keep is None else jnp.sum(keep, dtype=jnp.int32)
    return _per_platform(lambda: n * k, lambda: jnp.int32(0))
