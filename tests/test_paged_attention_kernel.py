"""The paged attention kernel (Pallas interpret mode on the CPU) against its
gather-and-softmax oracle, at the KV-head widths of glm4-9b, Granite 4.0-H
and Phi-3 medium, with head_dim 128 and blocks of 16.

Each batch holds slots of length 0, 1, exactly a block, a partial last
block, a long table and a full table; unallocated entries (-1) past each
length; and two slots that map the same prefix blocks.  A chunk of ``C``
rows must equal ``C`` one-row (decode) calls bit for bit, the invariant
that keeps greedy speculative serving token-exact.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import ops
from repro.kernels.paged_attention.ref import paged_attention_ref

BS, HD, MB = 16, 128, 20            # cap 320: two chunks at Kv 2, three at 8
WIDTHS = {"glm4": (2, 16), "granite": (8, 4), "phi3": (10, 4)}


def _case(Kv, G, C, seed=0, n=2):
    """Pools, table, fresh rows and queries for a batch of slots whose
    ``start`` runs over the lengths above (``start + C`` rows in all)."""
    cap = MB * BS
    starts = np.array([0, 1, BS, BS + 7, 250, cap - C, BS + 7], np.int32)
    B = len(starts)
    NB = B * MB + 3
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def rnd(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)

    pk = rnd(ks[0], (n, NB + 1, BS, Kv, HD))
    pv = rnd(ks[1], (n, NB + 1, BS, Kv, HD))
    q = rnd(ks[2], (B, C, Kv * G, HD))
    kn = rnd(ks[3], (B, C, Kv, HD))
    vn = rnd(ks[4], (B, C, Kv, HD))
    ids = np.random.RandomState(seed).permutation(NB)[:B * MB]
    tbl = ids.reshape(B, MB)
    tbl[-1, :1] = tbl[3, :1]        # the last slot shares slot 3's prefix
    used = np.arange(MB)[None] * BS < (starts + C)[:, None]
    tbl = np.where(used, tbl, -1).astype(np.int32)
    return (q, kn, vn, pk, pv, jnp.int32(1), jnp.asarray(tbl),
            jnp.asarray(starts))


@pytest.mark.parametrize("C", [1, 3])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_kernel_matches_gather(width, C):
    args = _case(*WIDTHS[width], C)
    got = jax.jit(ops.paged_attention_kernel)(*args)
    want = paged_attention_ref(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("width", list(WIDTHS))
def test_chunk_row_is_decode_bitwise(width):
    """Row ``j`` of a 3-row call equals the one-row call at ``start + j``
    whose pool holds rows ``start .. start + j - 1``, as decode's writes
    leave it."""
    C = 3
    q, kn, vn, pk, pv, layer, tbl, start = _case(*WIDTHS[width], C, seed=1)
    run = jax.jit(ops.paged_attention_kernel)
    chunk = np.asarray(run(q, kn, vn, pk, pv, layer, tbl, start))
    t = np.asarray(tbl)
    for j in range(C):
        pos = np.asarray(start)[:, None] + np.arange(j)[None]   # written
        blk, off = np.take_along_axis(t, pos // BS, 1), pos % BS
        pkj = pk.at[layer, blk.ravel(), off.ravel()].set(
            kn[:, :j].reshape(-1, *kn.shape[2:]))
        pvj = pv.at[layer, blk.ravel(), off.ravel()].set(
            vn[:, :j].reshape(-1, *vn.shape[2:]))
        one = run(q[:, j:j + 1], kn[:, j:j + 1], vn[:, j:j + 1], pkj, pvj,
                  layer, tbl, start + j)
        np.testing.assert_array_equal(chunk[:, j], np.asarray(one)[:, 0])


def test_cpu_lowering_runs_the_gather():
    """On the CPU the op is the oracle itself, value for value."""
    args = _case(*WIDTHS["glm4"], 1)
    got = jax.jit(ops.paged_attention)(*args)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(paged_attention_ref(*args)))


@pytest.mark.parametrize("Kv,dtype,window,fits", [
    (2, jnp.bfloat16, 0, True), (8, jnp.bfloat16, 0, True),
    (10, jnp.bfloat16, 0, False),       # 5 words a token: padded tiles
    (2, jnp.float32, 0, False), (2, jnp.float8_e4m3fn, 0, False),
    (2, jnp.bfloat16, 64, False)])
def test_kernel_fits_layouts(Kv, dtype, window, fits):
    pk = jnp.zeros((2, 9, BS, Kv, HD), dtype)
    tbl = jnp.zeros((4, MB), jnp.int32)
    assert ops.kernel_fits(pk, tbl, window) is fits


def test_rows_read_counts_the_table_on_the_cpu():
    q, kn, vn, pk, pv, layer, tbl, start = _case(*WIDTHS["glm4"], 1)
    B = tbl.shape[0]
    assert int(jax.jit(ops.rows_read)(start, pk, tbl)) == B * MB * BS
