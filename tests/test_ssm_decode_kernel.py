"""The Mamba-2 decode-step kernel (Pallas interpret mode on the CPU) against
the XLA step that ``ssm.mamba_decode`` runs (``ref.step``), at the
granite-4.0-h-small cell's head shapes (128 heads of 64, state 128) and at
a small one, on a stacked state of three layers stepped at layer 1.

Then the whole engine: the tiny hybrid configurations with a state of 128
(the kernel's gate wants a lane-wide state) serve the same greedy tokens
with the kernel in their decode steps as through the XLA path and the
host loop, and count the states the kernel updated.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.data import LanguageSpec, sample_batch
from repro.engine import Engine, serve_host_loop
from repro.kernels.ssm_decode import ops
from repro.kernels.ssm_decode.ref import ssm_decode_ref, step
from repro.models import build_model
from repro.models.lm import layer_plan

SHAPES = {"granite": (128, 64, 128), "small": (16, 8, 128)}   # nh, P, N
LAYER = 1
# slots decoding (1) or not (0)
KEEPS = {"middle": (1, 0, 1, 1), "leading": (0, 0, 1, 1),
         "trailing": (1, 1, 0, 0), "alone": (0, 1, 0, 0),
         "none": (0, 0, 0, 0), "all": (1, 1, 1, 1)}


def _case(nh, P, N, keep="middle", n=3, dtype=jnp.float32):
    """Stacked state of four slots, the step's inputs, and ``keep``."""
    keep = jnp.asarray(KEEPS[keep], bool)
    B = keep.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    h = jax.random.normal(ks[0], (n, B, nh, P, N), jnp.float32).astype(dtype)
    x = jax.random.normal(ks[1], (B, nh, P), jnp.float32)
    Bv = jax.random.normal(ks[2], (B, N), jnp.float32)
    C = jax.random.normal(ks[3], (B, N), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (B, nh)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[5], (nh,)))
    return h, x, Bv, C, dt, A, keep


def _kernel(h, x, Bv, C, dt, A, keep):
    return jax.jit(ops.ssm_decode_kernel)(h, LAYER, keep, x, Bv, C, dt, A)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_matches_xla_step(shape):
    """The new state and ``y = C h`` of the kept slots equal the XLA step's
    within float32 rounding (the kernel multiplies in another order)."""
    h, x, Bv, C, dt, A, keep = _case(*SHAPES[shape])
    assert ops.kernel_fits(h)
    got_h, got_y = _kernel(h, x, Bv, C, dt, A, keep)
    want_h, want_y = step(h[LAYER], x, Bv, C, dt, A)
    k = np.asarray(keep)
    for got, want in ((got_h[LAYER][k], want_h[k]), (got_y[k], want_y[k])):
        err = float(jnp.max(jnp.abs(got - want)))
        assert err <= 1e-5 * float(jnp.max(jnp.abs(want))), err


@pytest.mark.parametrize("keep", list(KEEPS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_touches_only_kept_slots_of_its_layer(shape, keep):
    """A slot outside ``keep`` keeps its state bit for bit, wherever it
    sits among the kept ones, and so does every other layer of the stack;
    the whole form (``ref``) agrees."""
    h, x, Bv, C, dt, A, keep = _case(*SHAPES[shape], keep=keep)
    got_h, _ = _kernel(h, x, Bv, C, dt, A, keep)
    want_h, _ = ssm_decode_ref(h, LAYER, keep, x, Bv, C, dt, A)
    others = np.array([l for l in range(h.shape[0]) if l != LAYER])
    out = np.flatnonzero(~np.asarray(keep))
    assert bool(jnp.all(got_h[others] == h[others]))
    assert bool(jnp.all(got_h[LAYER, out] == h[LAYER, out]))
    assert bool(jnp.all(want_h[LAYER, out] == h[LAYER, out]))
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape, dtype, fits", [
    ((16, 8, 128), jnp.float32, True),
    ((16, 8, 128), jnp.bfloat16, False),       # a bf16 state
    ((8, 16, 16), jnp.float32, False),         # state 16: not lane-wide
    ((16, 4, 128), jnp.float32, False),        # head of 4: not a sublane
    ((2, 1024, 128), jnp.float32, False),      # 512-KiB heads: no tile fits
], ids=["fits", "bf16", "narrow_state", "narrow_head", "wide_head"])
def test_gate_sends_other_states_to_xla(monkeypatch, shape, dtype, fits):
    """On a kernel platform (here the CPU, added to them), a state that
    fails the gate takes the XLA step, and one that passes runs the
    kernel."""
    monkeypatch.setattr(ops, "KERNEL_PLATFORMS", ("tpu", "cpu"))
    h, x, Bv, C, dt, A, keep = _case(*shape, dtype=dtype)
    assert ops.kernel_fits(h) == fits
    jaxpr = str(jax.make_jaxpr(lambda h: ops.ssm_decode(
        h, LAYER, keep, x, Bv, C, dt, A))(h))
    assert ("pallas_call" in jaxpr) == fits
    if not fits:
        got = ops.ssm_decode(h, LAYER, keep, x, Bv, C, dt, A)
        want = ssm_decode_ref(h, LAYER, keep, x, Bv, C, dt, A)
        for g, w in zip(got, want):
            assert bool(jnp.all(g == w))


@pytest.mark.parametrize("shape, hb", [((128, 64, 128), 16),
                                       ((16, 8, 128), 16),
                                       ((40, 64, 128), 8),
                                       ((1, 1024, 128), 1),
                                       ((2, 1024, 128), 0)])
def test_heads_per_block(shape, hb):
    """At most ``TILE_BYTES`` of state a program: the most heads that fit,
    a multiple of 8 dividing the heads or all of them (0: none fits)."""
    assert ops.heads_per_block(*shape) == hb


def _tiny(arch):
    cfg = dataclasses.replace(reduced(get_arch(arch)), ssm_state=128)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("arch", ["granite-4.0-h-small", "jamba-v0.1-52b"])
def test_engine_decodes_through_the_kernel(monkeypatch, arch):
    """The tiny hybrids (Granite: 9 Mamba-2 layers of 10; Jamba: 7 of 8),
    five requests on two slots: with the kernel in the decode steps the
    engine serves the XLA path's greedy tokens and the host loop's, and
    counts a state for every decode emission in every Mamba-2 layer (the
    first token of a request comes from its last prefill chunk)."""
    cfg, model, params = _tiny(arch)
    spec = LanguageSpec(vocab=cfg.vocab_size)
    prompts = [sample_batch(jax.random.PRNGKey(i), spec, 1, L)[0]
               for i, L in enumerate([9, 40, 12, 33, 5])]
    gen = 5

    def serve():
        eng = Engine(model, params, slots=2, cache_len=48, k_steps=2,
                     block_size=8, chunk_size=32)
        return eng.serve(prompts, gen_tokens=gen, return_stats=True)

    outs, st = serve()
    assert st["counters"]["ssm_kernel_states"] == 0
    monkeypatch.setattr(ops, "KERNEL_PLATFORMS", ("tpu", "cpu"))
    outs_k, st_k = serve()
    assert outs_k == outs
    assert outs == serve_host_loop(model, params, prompts, batch=2,
                                   gen_tokens=gen, cache_len=48)
    _, _, specs, n_periods = layer_plan(cfg)
    mamba = n_periods * sum(m == "mamba" for m, _ in specs)
    assert st_k["counters"]["tokens"] == len(prompts) * gen
    assert st_k["counters"]["ssm_kernel_states"] == \
        len(prompts) * (gen - 1) * mamba
