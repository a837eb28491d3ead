"""Program spans on the profiler's clock and named scopes of device work.

* a :func:`repro.telemetry.trace.span` lands at the same instant in a
  ``jax.profiler`` trace and in the Chrome JSON of an attached Tracer;
* ``Engine.serve`` opens ``engine.*`` spans, its dispatch variants compile
  to modules named after them, and ``Engine.hlo_texts()`` returns the
  executables that ran, whose ops ``op_scopes`` places under ``qlinear``,
  ``attention`` and ``prefill_chunk`` (never under both of the first two);
* ``quantize()`` opens ``quantize.*`` spans and counts its blocking reads;
* tracer, metrics and profiler on or off change no output, no compile
  count and no host sync.
"""
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs import QuantConfig, get_arch, reduced
from repro.core.metrics import PARTIAL_KEYS
from repro.data import LanguageSpec, sample_batch
from repro.engine import Engine
from repro.models import build_model
from repro.quantize import quantize
from repro.telemetry import MetricsRegistry, Tracer
from repro.telemetry.scopes import SCOPES, op_scopes, scope_path
from repro.telemetry.trace import span, xplane_origin_us

KEY = jax.random.PRNGKey(0)


def _profile(tmp_path, fn):
    """Run ``fn`` under the profiler; (its result, the trace)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    f = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    return out, ProfileData.from_file(str(f))


def _events(pd):
    for plane in pd.planes:
        for line in plane.lines:
            yield from line.events


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_arch("glm4-9b"))
    model = build_model(cfg)
    params = model.init(KEY)
    spec = LanguageSpec(vocab=cfg.vocab_size)
    prompts = [sample_batch(jax.random.PRNGKey(50 + i), spec, 1, 20)[0]
               for i in range(4)]
    return cfg, model, params, prompts


def test_span_lands_on_the_profiler_clock(tmp_path):
    import time
    tr = Tracer()

    def run():
        with span("test", "probe", tr, k=1):
            time.sleep(0.02)

    _, pd = _profile(tmp_path, run)
    ev = next(e for e in _events(pd) if e.name == "test.probe")
    assert dict(ev.stats) == {"k": 1}
    x = next(e for e in tr.to_dict()["traceEvents"] if e["name"] == "probe")
    xplane_ts = xplane_origin_us(pd) + ev.start_ns / 1e3
    assert abs(xplane_ts - x["ts"]) < 1e3                  # within 1 ms
    assert abs(ev.duration_ns / 1e3 - x["dur"]) < 1e3
    assert x["dur"] >= 2e4 and x["args"] == {"k": 1}


@pytest.fixture(scope="module")
def served(tiny, tmp_path_factory):
    """A chunked, prefix-cached engine served once warm and once under the
    profiler: (engine, trace, its hlo_texts, compile counts before)."""
    cfg, model, params, prompts = tiny
    eng = Engine(model, params, slots=2, cache_len=48, k_steps=4,
                 block_size=8, prefix_cache=True, chunk_size=8)
    eng.serve(prompts, gen_tokens=6)
    _, pd = _profile(tmp_path_factory.mktemp("trace"),
                     lambda: eng.serve(prompts, gen_tokens=6))
    counts = eng.compile_counts()
    return eng, pd, eng.hlo_texts(), counts


def test_serve_records_engine_spans_and_dispatch_names(served):
    eng, pd, _, _ = served
    names = Counter(e.name for e in _events(pd))
    for s in ("engine.admit", "engine.dispatch", "engine.sync",
              "engine.host"):
        assert names[s] > 0, s
    assert names["engine.dispatch"] == names["engine.sync"] \
        == names["engine.host"]
    assert names["engine.admit"] == 4
    modules = {dict(e.stats).get("hlo_module") for e in _events(pd)}
    assert {"jit_chunk_dispatch", "jit_decode_dispatch"} <= modules
    assert "jit_dispatch" not in modules


def test_hlo_texts_are_the_executables_that_ran(served):
    eng, pd, texts, counts = served
    assert set(texts) == {"jit_chunk_dispatch", "jit_decode_dispatch"}
    assert eng.compile_counts() == counts          # lowering adds no entry
    ran = {(s["hlo_module"], s["hlo_op"]) for s in
           (dict(e.stats) for e in _events(pd)) if "hlo_op" in s}
    from repro.analysis.hlo import HloModule
    for mod, text in texts.items():
        instrs = {i.name for c in HloModule(text).computations.values()
                  for i in c}
        ops = {op for m, op in ran if m == mod}
        assert ops and ops <= instrs, mod


def test_op_scopes_place_the_dispatch_ops(served):
    _, _, texts, _ = served
    chunk = op_scopes(texts["jit_chunk_dispatch"])
    decode = op_scopes(texts["jit_decode_dispatch"])
    found = {s for m in (chunk, decode) for p in m.values()
             for s in p.split("/")}
    assert {"qlinear", "attention", "prefill_chunk", "keep_rows",
            "sample"} <= found
    assert not any("prefill_chunk" in p for p in decode.values())
    for m in (chunk, decode):
        assert not any({"qlinear", "attention"} <= set(p.split("/"))
                       for p in m.values())


def test_scope_path_reads_op_names():
    assert scope_path("jit(f)/while/body/qlinear/dot_general") == "qlinear"
    assert scope_path("jit(f)/prefill_chunk/attention/attention/exp") \
        == "prefill_chunk/attention"
    assert scope_path("jit(f)/while/body/add") == ""
    assert scope_path("jit(f)/prefill_chunk/moe/qlinear/dot") \
        == "prefill_chunk/moe/qlinear"
    assert scope_path("jit(f)/while/body/attention/cond/branch_0_fun/"
                      "paged_attn/cond/branch_0_fun/paged_attention/"
                      "pallas_call") == "attention/paged_attn"
    assert SCOPES == ("qlinear", "attention", "mamba", "moe",
                      "prefill_chunk", "keep_rows", "sample", "paged_attn")


def test_hlo_texts_needs_a_served_prefix_engine(tiny):
    """Only dispatches that have run have a text: none before a serve."""
    cfg, model, params, _ = tiny
    eng = Engine(model, params, slots=2, cache_len=48, k_steps=4,
                 block_size=8, prefix_cache=True, chunk_size=8)
    assert eng.hlo_texts() == {}


@pytest.mark.parametrize("loop", ["paged", "spec"])
def test_hlo_texts_of_an_engine_without_prefix_cache(tiny, tmp_path, loop):
    """An engine that keeps no cache between serves gives the executables
    its dispatches ran — the chunk dispatch that prefilled the prompts and
    the dispatch that decoded after them, plain or speculative — as the
    profiler names their ops."""
    cfg, model, params, prompts = tiny
    kw, mods = {}, ("jit_chunk_dispatch", "jit_decode_dispatch")
    if loop == "spec":
        draft, _ = quantize(params, None,
                            QuantConfig(method="absmax",
                                        granularity="channel"),
                            mode="storage", out_dtype="bfloat16")
        kw, mods = (dict(n_spec=2, draft_params=draft),
                    ("jit_spec_chunk_dispatch", "jit_spec_dispatch"))
    eng = Engine(model, params, slots=2, cache_len=48, k_steps=4,
                 block_size=8, **kw)
    # 12 tokens outlast the first dispatch: at most 1 + 3 rounds of 3
    outs, pd = _profile(tmp_path, lambda: eng.serve(prompts, gen_tokens=12))
    counts = eng.compile_counts()
    texts = eng.hlo_texts()
    assert set(texts) == set(mods)
    assert eng.compile_counts() == counts
    decode = mods[1]
    ran = {s["hlo_op"] for s in (dict(e.stats) for e in _events(pd))
           if s.get("hlo_module") == decode and "hlo_op" in s}
    from repro.analysis.hlo import HloModule
    instrs = {i.name for c in HloModule(texts[decode])
              .computations.values() for i in c}
    assert ran and ran <= instrs
    assert any("qlinear" in p.split("/") for p in
               op_scopes(texts[decode]).values())
    assert eng.serve(prompts, gen_tokens=12) == outs


def _pair():
    k1, k2, k3 = jax.random.split(KEY, 3)
    post = {"blk": {"w": jax.random.normal(k1, (48, 64)) * 0.05,
                    "stack": jax.random.normal(k2, (3, 32, 48)) * 0.05},
            "norm_w": jnp.ones((48,))}
    base = jax.tree.map(lambda p: p - 0.002 * jax.random.normal(k3, p.shape)
                        if p.ndim >= 2 else p, post)
    return post, base


def test_quantize_counts_its_host_reads(tmp_path, monkeypatch):
    """Two eligible leaves: per leaf a sum of each partial key at both
    scales, the alpha, the four metric means at both scales; then the four
    global metrics at both scales.  The alpha is one ``device_get``, every
    other read one ``float`` of a device scalar."""
    from jax._src.array import ArrayImpl
    post, base = _pair()
    qc = QuantConfig(method="daq", metric="sign", granularity="channel")
    reads = []
    get, to_float = jax.device_get, ArrayImpl.__float__
    monkeypatch.setattr(jax, "device_get",
                        lambda x: reads.append(1) or get(x))
    monkeypatch.setattr(ArrayImpl, "__float__",
                        lambda x: reads.append(1) or to_float(x))
    (_, rep), pd = _profile(tmp_path, lambda: quantize(post, base, qc))
    per_leaf = 2 * len(PARTIAL_KEYS) + 1 + 2 * 4
    assert rep.n_quantized == 2 and rep.n_skipped == 1
    assert rep.host_reads == 2 * per_leaf + 2 * 4 == len(reads) == 50
    spans = [e for e in _events(pd) if e.name.startswith("quantize.")]
    assert Counter(e.name for e in spans) == {
        "quantize.leaf": 2, "quantize.search": 2, "quantize.report": 2,
        "quantize.global": 1}
    leaves = [e for e in spans if e.name == "quantize.leaf"]
    for e in spans:
        if e.name in ("quantize.search", "quantize.report"):
            assert any(lf.start_ns <= e.start_ns and e.start_ns
                       + e.duration_ns <= lf.start_ns + lf.duration_ns
                       for lf in leaves), e.name
    assert {dict(e.stats)["leaf"] for e in leaves} == {"blk/stack",
                                                        "blk/w"}


@pytest.mark.parametrize("loop", ["paged", "spec"])
def test_serve_parity_with_tracing_on_and_off(tiny, tmp_path, loop):
    """The plain paged loop and the chunked speculative loop give the same
    tokens, compile counts, host syncs and stats with tracer, metrics and
    profiler on as with all three off; the tracer holds the engine spans
    and counter tracks."""
    cfg, model, params, prompts = tiny
    kw = dict(slots=2, cache_len=48, k_steps=4, block_size=8)
    if loop == "spec":
        draft, _ = quantize(params, None,
                            QuantConfig(method="absmax",
                                        granularity="channel"),
                            mode="storage", out_dtype="bfloat16")
        kw.update(chunk_size=8, prefix_cache=True, n_spec=2,
                  draft_params=draft)
    tr = Tracer()
    e_on = Engine(model, params, metrics=MetricsRegistry(), tracer=tr, **kw)
    e_off = Engine(model, params, **kw)
    (outs_on, st_on), _ = _profile(
        tmp_path, lambda: e_on.serve(prompts, gen_tokens=6,
                                     return_stats=True))
    outs_off, st_off = e_off.serve(prompts, gen_tokens=6, return_stats=True)
    assert outs_on == outs_off
    assert st_on == st_off
    assert e_on.compile_counts() == e_off.compile_counts()
    evs = tr.to_dict()["traceEvents"]
    xs = Counter(e["name"] for e in evs if e["ph"] == "X")
    assert xs["dispatch"] == xs["sync"] == xs["host"] == st_on["dispatches"]
    assert xs["admit"] > 0
    series = {e["name"] for e in evs if e["ph"] == "C"}
    assert {"tokens", "blocks"} <= series
    assert ("spec" in series) == (loop == "spec")
