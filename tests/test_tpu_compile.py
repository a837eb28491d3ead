"""Compile for a described TPU v5e (no chip attached) at glm4-9b widths.

The TPU compiler refuses what interpret mode accepts: misaligned block
shapes, too much fast memory, programs that do not fit the device.  These
tests compile the Pallas kernels and the DAQ per-leaf program for one v5e
chip, so such faults show up here instead of on the chip.

The topology is described inside a module-scoped fixture only: describing it
loads the TPU library, which one process at a time may hold.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import QuantConfig
from repro.quantize.api import LeafContext
from repro.quantize.daq import DAQQuantizer

D_MODEL, D_FF, VOCAB = 4096, 13696, 151552   # glm4-9b published widths


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _kernel_case(name):
    from repro.kernels.fp8_matmul.ops import matmul_fp8_2d
    from repro.kernels.fp8_quant.ops import quantize_fp8
    from repro.kernels.scale_search.ops import sweep
    K, N, bs = D_MODEL, D_FF, 128
    if name == "fp8_matmul":
        return matmul_fp8_2d, [((8, K), jnp.bfloat16),
                               ((K, N), jnp.float8_e4m3fn),
                               ((K // bs, N // bs), jnp.float32)]
    if name == "fp8_quant":
        return quantize_fp8, [((K, N), jnp.bfloat16), ((), jnp.float32)]
    return (lambda wp, wb, a: sweep(wp, wb, a)["tensor"],
            [((K, N), jnp.bfloat16), ((K, N), jnp.bfloat16),
             ((16,), jnp.float32)])


@pytest.mark.parametrize("name", ["fp8_matmul", "fp8_quant", "scale_search"])
def test_kernel_compiles_for_v5e(one_chip, name):
    """Each kernel lowers through Mosaic (not the interpreter) and compiles
    at glm4-9b's MLP width."""
    fn, specs = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [(VOCAB, D_MODEL), (4, D_MODEL, D_FF)],
                         ids=["embed", "stacked_mlp"])
def test_daq_prepare_fits_v5e(one_chip, shape):
    """DAQ's per-leaf program holds its bf16 inputs, its fp8 output and a
    bounded working set: candidates are scored over row blocks, stacked
    layers one at a time, and no fp32 dequantized copy is built.  Before
    that repair the embed leaf compiled to 2.48 GB arguments + 4.97 GB
    temporaries + 3.1 GB output."""
    q = QuantConfig(method="daq", metric="sign", fmt="fp8_e4m3",
                    granularity="channel")
    leaf = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    prepare = lambda wp, wb: DAQQuantizer().prepare(
        LeafContext("embed/embed", wp, wb, q))
    m = jax.jit(prepare).lower(leaf, leaf).compile().memory_analysis()
    args = m.argument_size_in_bytes
    # inputs + fp8 output (a quarter of the inputs) + 256 MiB working set
    budget = args + args // 4 + (256 << 20)
    used = args + m.temp_size_in_bytes + m.output_size_in_bytes
    assert used <= budget, (args, m.temp_size_in_bytes,
                            m.output_size_in_bytes)


def test_fp8_round_trip_survives_v5e_fusion(one_chip):
    """Q_s(W) must round to fp8 on the chip.  XLA's TPU fusions may keep
    an intermediate in higher precision, so an fp8 code that is converted
    straight back inside one fusion is never rounded (DAQ then scores every
    candidate as lossless).  Every fp8 code in the compiled DAQ scoring has
    to leave its fusion as a materialised fp8 result."""
    import re
    from repro.core.search import search_scale
    q = QuantConfig(metric="sign", granularity="channel")
    leaf = jax.ShapeDtypeStruct((D_MODEL, D_FF), jnp.bfloat16,
                                sharding=one_chip)
    hlo = search_scale.lower(leaf, leaf, q).compile().as_text()
    fused = re.findall(r"\n(%fused_computation\S* .*?\n})", hlo, re.S)
    converts = [line for comp in fused for line in comp.split("\n")
                if re.search(r"= f8e4m3fn\[[^\]]*\]\S* convert\(", line)]
    assert converts, "no fp8 rounding left in the compiled search"
    inner = [c for c in converts if not c.lstrip().startswith("ROOT")]
    assert not inner, inner[:2]


@pytest.mark.parametrize("B,Kv,G,MB", [(64, 2, 16, 48), (24, 8, 4, 144)],
                         ids=["glm4", "granite"])
def test_paged_attention_kernel_compiles_for_v5e(one_chip, B, Kv, G, MB):
    """The paged attention kernel lowers through Mosaic at the serving
    cells' widths (glm4-9b: 2 KV heads of 16 query heads; Granite 4.0-H:
    8 of 4), one decode row and a verify chunk of 4, against the cells'
    stacked pools, which it reads in place: no copy of a pool."""
    import re
    from repro.kernels.paged_attention.ops import paged_attention_kernel
    n, NB, hd = 4, 3072, 128
    for C in (1, 4):
        specs = [((B, C, Kv * G, hd), jnp.bfloat16),
                 ((B, C, Kv, hd), jnp.bfloat16),
                 ((B, C, Kv, hd), jnp.bfloat16),
                 ((n, NB + 1, 16, Kv, hd), jnp.bfloat16),
                 ((n, NB + 1, 16, Kv, hd), jnp.bfloat16),
                 ((), jnp.int32), ((B, MB), jnp.int32), ((B,), jnp.int32)]
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        text = jax.jit(paged_attention_kernel).lower(*args).compile() \
            .as_text()
        assert "tpu_custom_call" in text
        pool = re.compile(rf"bf16\[{n},{NB + 1},16,{Kv},{hd}\]\S* copy\(")
        assert not pool.search(text)


def _paged_dispatch_compiled(sharding, entry, n_layers):
    """``entry`` of the engine that serves glm4-9b from fp8 (channel
    scales) through the paged, prefix-cached, chunked path: 64 slots,
    cache_len 768, 3072 blocks of 16, chunk 64, 8 steps a dispatch.
    Returns (compiled, per-layer pool shape)."""
    import dataclasses
    from repro.configs import get_arch
    from repro.core.policy import path_str, should_quantize
    from repro.engine import Engine
    from repro.engine.scheduler import init_slot_state
    from repro.models import build_model
    from repro.quant_runtime.qparams import QuantizedTensor

    model = build_model(dataclasses.replace(get_arch("glm4-9b"),
                                            n_layers=n_layers))

    def sds(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=sharding)

    def fp8(path, leaf):
        if not should_quantize(path_str(path), leaf, ("norm", "bias")):
            return sds(leaf)
        scale = jax.ShapeDtypeStruct(leaf.shape[:-2] + (1, leaf.shape[-1]),
                                     jnp.float32, sharding=sharding)
        return QuantizedTensor(data=sds(leaf, jnp.float8_e4m3fn),
                               scale=scale, fmt="fp8_e4m3",
                               granularity="channel", block_size=128,
                               out_dtype="bfloat16")

    params = jax.tree_util.tree_map_with_path(
        fp8, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = Engine(model, params, slots=64, cache_len=768, num_blocks=3072,
                 prefix_cache=True, chunk_size=64, block_size=16,
                 k_steps=8)
    cache = jax.tree.map(sds, jax.eval_shape(
        lambda: model.init_paged_cache(64, 768, block_size=16,
                                       num_blocks=3072)))
    state = jax.tree.map(sds, jax.eval_shape(
        lambda: init_slot_state(64, prompt_cap=768)))
    key = sds(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    fn = eng.entry_points()[entry]["fn"]
    pool = cache["stack"]["L0"]["pk"].shape[1:]
    return fn.lower(params, state, cache, key).compile(), pool


@pytest.mark.parametrize("entry", ["_dispatch", "_dispatch_chunk"])
def test_paged_dispatch_updates_pools_in_place(one_chip, entry):
    """The decode and chunk dispatches update the stacked K/V pools in
    place through the layer scan: no instruction copies a buffer of a
    layer's pool or of the stacked pools, or passes one through a
    conditional, and the temporaries do not grow by a layer's pools per
    layer.  A per-layer slice of the pools out of the scan's inputs, a
    ``lax.cond`` over them or a relayout between the row scatter and the
    gather each shows here as such a copy."""
    import re
    compiled = {n: _paged_dispatch_compiled(one_chip, entry, n)
                for n in (2, 4)}
    (c2, pool), (c4, _) = compiled[2], compiled[4]
    dims = ",".join(map(str, pool))
    shape = re.compile(rf"bf16\[(\d+,)?{dims}\]")
    op = re.compile(r"\s(copy|copy-start|conditional)\(")
    bad = [ln.strip()[:160] for ln in c2.as_text().splitlines()
           if shape.search(ln) and op.search(ln)]
    assert not bad, bad
    layer_kv = 2 * math.prod(pool) * 2               # K and V, bf16
    grow = (c4.memory_analysis().temp_size_in_bytes
            - c2.memory_analysis().temp_size_in_bytes)
    assert grow < layer_kv, (grow, layer_kv)


def test_paged_decode_reads_the_pools_through_the_kernel(one_chip):
    """glm4-9b's decode steps attend through the paged attention kernel: no
    instruction builds a slot's whole table (``[64, 48, 16, 2, 128]`` or
    ``[64, 768, 2, 128]``) in the decode dispatch, and in the chunk
    dispatch only its prefill chunk (flash tiles over the table) still
    gathers the table's blocks."""
    import re
    from repro.telemetry.scopes import scope_path
    table = re.compile(r"= bf16\[64,(48,16|768),2,128\]\S* (gather|fusion)\(")
    blocks = re.compile(r"= bf16\[64,48,16,2,128\]\S* gather\(")
    for entry in ("_dispatch", "_dispatch_chunk"):
        text = _paged_dispatch_compiled(one_chip, entry, 2)[0].as_text()
        assert "tpu_custom_call" in text, entry
        if entry == "_dispatch":
            assert not table.search(text), table.search(text).group(0)
        built = [scope_path(re.search(r'op_name="([^"]*)"', ln).group(1))
                 for ln in text.splitlines() if blocks.search(ln)]
        assert all("prefill_chunk" in p for p in built), (entry, built)


def _hybrid_dispatch_compiled(sharding, entry, n_layers):
    """``entry`` of an engine that serves a Granite 4.0-H hybrid at its
    published widths (Mamba-2: 128 heads of 64, state 128; 9 of 72 experts
    held) from fp8, with a period of two layers (Mamba-2, then attention):
    the cell's 24 slots, cache_len 512, chunk 256, 2 steps a dispatch.  Returns
    (compiled, per-layer SSM state shape)."""
    import dataclasses
    from repro.configs import get_arch
    from repro.core.policy import path_str, should_quantize
    from repro.engine import Engine
    from repro.engine.scheduler import init_slot_state
    from repro.models import build_model
    from repro.quant_runtime.qparams import QuantizedTensor

    model = build_model(dataclasses.replace(
        get_arch("granite-4.0-h-small"), attn_every=2, n_layers=n_layers))

    def sds(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=sharding)

    skip = ("norm", "bias", "router", "a_log", "conv")

    def fp8(path, leaf):
        if not should_quantize(path_str(path), leaf, skip):
            return sds(leaf)
        scale = jax.ShapeDtypeStruct(leaf.shape[:-2] + (1, leaf.shape[-1]),
                                     jnp.float32, sharding=sharding)
        return QuantizedTensor(data=sds(leaf, jnp.float8_e4m3fn),
                               scale=scale, fmt="fp8_e4m3",
                               granularity="channel", block_size=128,
                               out_dtype="bfloat16")

    params = jax.tree_util.tree_map_with_path(
        fp8, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = Engine(model, params, slots=24, cache_len=512, num_blocks=768,
                 chunk_size=256, block_size=16, k_steps=2)
    cache = jax.tree.map(sds, jax.eval_shape(
        lambda: model.init_paged_cache(24, 512, block_size=16,
                                       num_blocks=768)))
    state = jax.tree.map(sds, jax.eval_shape(
        lambda: init_slot_state(24, prompt_cap=512, moe=True)))
    key = sds(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    fn = eng.entry_points()[entry]["fn"]
    return (fn.lower(params, state, cache, key).compile(),
            cache["stack"]["L0"]["h"].shape[1:])


@pytest.mark.parametrize("entry", ["_dispatch", "_dispatch_chunk"])
def test_hybrid_dispatch_updates_ssm_state_in_place(one_chip, entry):
    """The decode and chunk dispatches update the per-slot Mamba-2 state in
    place through the layer scan: no instruction copies a buffer of a
    layer's state or of the stacked state (a slice out of the scan's
    inputs written back through its outputs, a per-slot select of the whole
    state, or a relayout for the chunk's SSD scan each shows here as such a
    copy), and the temporaries grow by less than the state of the Mamba-2
    layers added (four periods against eight)."""
    import re
    compiled = {n: _hybrid_dispatch_compiled(one_chip, entry, n)
                for n in (8, 16)}
    (c2, h), (c4, _) = compiled[8], compiled[16]
    shape = re.compile(r"f32\[(\d+,)?%s\]" % ",".join(map(str, h)))
    op = re.compile(r"\s(copy|copy-start|conditional)\(")
    # an async copy into another memory space (``S(1)``) is a prefetch,
    # not a copy in HBM
    bad = [ln.strip()[:160] for ln in c2.as_text().splitlines()
           if shape.search(ln) and op.search(ln) and "S(1)" not in ln]
    assert not bad, bad
    # eight layers more hold four Mamba-2 layers' float32 state more: a
    # copy of the stacked state would grow the temporaries by all of it
    added = 4 * 4 * math.prod(h)
    grow = (c4.memory_analysis().temp_size_in_bytes
            - c2.memory_analysis().temp_size_in_bytes)
    assert grow < added, (grow, added)


@pytest.mark.parametrize("tokens", [24, 24 * 256], ids=["decode", "chunk"])
def test_moe_row_tile_is_the_grouped_matmul_tile(one_chip, tokens):
    """``moe.row_tile``, which the expert layer's ``moe_rows`` counter
    counts padding by, is the row tile of the grouped matmul that the TPU
    compiles ``jax.lax.ragged_dot`` to, at the Granite 4.0-H cell's token
    counts (24 slots decoding; 24 slots prefilling a 256-token chunk).  The
    kernel's metadata lists one entry per tile it may visit: the ``M /
    tile`` row tiles plus ``groups - 1`` that two groups share."""
    import re
    from repro.configs import get_arch
    from repro.models import moe as MOE
    from repro.quant_runtime.qparams import QuantizedTensor

    cfg = get_arch("granite-4.0-h-small")
    H = cfg.resolved_experts_held

    def sds(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=one_chip)

    def fp8(path, leaf):
        if "router" in jax.tree_util.keystr(path) or leaf.ndim < 3:
            return sds(leaf)
        scale = jax.ShapeDtypeStruct(leaf.shape[:-2] + (1, leaf.shape[-1]),
                                     jnp.float32, sharding=one_chip)
        return QuantizedTensor(data=sds(leaf, jnp.float8_e4m3fn),
                               scale=scale, fmt="fp8_e4m3",
                               granularity="channel", block_size=128,
                               out_dtype="bfloat16")

    p = jax.tree_util.tree_map_with_path(fp8, jax.eval_shape(
        lambda: MOE.init_moe(jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    x = jax.ShapeDtypeStruct((24, tokens // 24, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(lambda p, x: MOE.apply_moe_dropless(p, x, cfg)).lower(
        p, x).compile().as_text()
    meta = [ln for ln in text.splitlines() if "ragged-dot-metadata = (" in ln]
    assert meta, "ragged_dot did not compile to the grouped-matmul kernel"
    offsets, tiles = (int(n) for n in
                      re.findall(r"s32\[(\d+)\]", meta[0])[:2])
    assert offsets == H + 1
    M = min(tokens, MOE.TOKEN_BLOCK) * min(cfg.top_k, H)
    assert M % (tiles - (H - 1)) == 0
    assert MOE.row_tile(M) == M // (tiles - (H - 1))
