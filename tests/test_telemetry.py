"""Telemetry tests: histogram bucket math and closed-form percentiles,
metrics snapshot schema, Chrome-trace schema (monotonic per-track clocks),
and the zero-cost contract of the device counters — metrics on vs off must
produce identical outputs, identical host-sync counts and identical jit
cache sizes, because the host-side layers only *read* values the serve
loop already fetched.
"""
import json
import math

import jax
import pytest

from repro.configs import get_arch, reduced
from repro.data import LanguageSpec, sample_batch
from repro.engine import Engine
from repro.models import build_model
from repro.telemetry import (COUNTER_KEYS, METRICS_SCHEMA, Histogram,
                             MetricsRegistry, Tracer)
from repro.telemetry.trace import clock_us, span
from repro.telemetry.metrics import log_bucket_edges

KEY = jax.random.PRNGKey(0)

_BUILT: dict = {}


def _setup(arch="glm4-9b"):
    if arch not in _BUILT:
        cfg = reduced(get_arch(arch))
        model = build_model(cfg)
        params = model.init(KEY)
        _BUILT[arch] = (cfg, model, params,
                        LanguageSpec(vocab=cfg.vocab_size))
    return _BUILT[arch]


# ---------------------------------------------------------------------------
# Histogram: bucket edges + percentiles (closed-form)
# ---------------------------------------------------------------------------

def test_log_bucket_edges_closed_form():
    lo, hi, n = 1e-3, 1e3, 6
    edges = log_bucket_edges(lo, hi, n)
    assert len(edges) == n + 1
    assert edges[0] == lo and edges[-1] == hi      # endpoints pinned exactly
    for i, e in enumerate(edges):
        assert e == pytest.approx(lo * (hi / lo) ** (i / n))
    ratios = [edges[i + 1] / edges[i] for i in range(n)]
    for r in ratios:                                # constant ratio
        assert r == pytest.approx((hi / lo) ** (1 / n))
    for bad in ((0.0, 1.0, 4), (2.0, 1.0, 4), (1.0, 1.0, 4)):
        with pytest.raises(ValueError):
            log_bucket_edges(*bad)
    with pytest.raises(ValueError):
        log_bucket_edges(1.0, 2.0, 0)


def test_histogram_bucket_membership():
    h = Histogram("t", lo=1.0, hi=100.0, n_buckets=4)
    # edges: 1, 100^(1/4)=3.162.., 10, 31.62.., 100
    for v in (0.5, 1.0, 3.0, 11.0, 99.0, 100.0, 250.0):
        h.observe(v)
    assert h.count == 7
    assert sum(h.bucket_counts) == h.count          # every sample bucketed
    assert h.bucket_counts == [1, 2, 0, 1, 1, 2]
    # every in-range sample sits inside its bucket's half-open interval
    for v in (1.0, 3.0, 11.0, 99.0):
        i = next(j for j in range(h.n_buckets)
                 if h.edges[j] <= v < h.edges[j + 1])
        assert h.edges[i] <= v < h.edges[i + 1]


def test_histogram_edge_values_never_misbucket():
    h = Histogram("e", lo=1e-4, hi=1e3, n_buckets=32)
    for i, e in enumerate(h.edges[:-1]):            # exact edge values
        h.observe(e)
        assert h.bucket_counts[1 + i] >= 1, f"edge {i} ({e}) misbucketed"
    assert sum(h.bucket_counts) == h.count


def test_percentiles_nearest_rank_closed_form():
    h = Histogram("p", lo=1e-3, hi=1e3)
    for v in range(1, 101):                          # 1..100
        h.observe(float(v))
    # nearest-rank over n=100: rank = ceil(q), value = rank
    assert h.percentile(50) == 50.0
    assert h.percentile(95) == 95.0
    assert h.percentile(99) == 99.0
    assert h.percentile(100) == 100.0
    assert h.percentile(0.5) == 1.0                  # rank floor at 1
    d = h.to_dict()
    assert d["count"] == 100
    assert d["min"] == 1.0 and d["max"] == 100.0
    assert d["sum"] == 5050.0
    assert (d["p50"], d["p95"], d["p99"]) == (50.0, 95.0, 99.0)
    # odd n: nearest-rank p50 of [1, 2, 3] is 2
    h3 = Histogram("q")
    for v in (3.0, 1.0, 2.0):
        h3.observe(v)
    assert h3.percentile(50) == 2.0
    assert math.ceil(50 / 100 * 3) == 2              # the rank formula


def test_percentile_empty_and_singleton():
    h = Histogram("s")
    assert h.percentile(50) is None
    assert "p50" not in h.to_dict()
    h.observe(2.5)
    assert h.percentile(50) == h.percentile(99) == 2.5


# ---------------------------------------------------------------------------
# Registry snapshot: stable schema, JSON round-trip
# ---------------------------------------------------------------------------

def test_registry_snapshot_schema(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(2)                          # get-or-create
    reg.gauge("g").set(3.5)
    reg.gauge("unset")                               # stays None -> n/a
    reg.histogram("h", unit="s").observe(0.1)
    snap = reg.snapshot()
    assert METRICS_SCHEMA == "repro.telemetry.metrics/v1"
    assert snap["schema"] == METRICS_SCHEMA
    assert snap["counters"] == {"a": 3}
    assert snap["gauges"] == {"g": 3.5, "unset": None}
    hd = snap["histograms"]["h"]
    assert hd["count"] == 1 and hd["unit"] == "s"
    assert len(hd["counts"]) == len(hd["edges"]) + 1  # under+overflow
    path = tmp_path / "metrics.json"
    reg.save(path)
    assert json.loads(path.read_text()) == snap      # plain JSON types only
    s = reg.summary()
    assert "unset: n/a" in s and "p50=" in s and "a: 3" in s


# ---------------------------------------------------------------------------
# Tracer: Chrome trace-event schema, monotonic per-track timestamps
# ---------------------------------------------------------------------------

def _check_chrome_trace(doc):
    """Schema assertions shared by the unit test and the serve test."""
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    per_track: dict = {}
    meta_tids = set()
    for ev in evs:
        assert {"name", "ph", "pid"} <= set(ev)
        if ev["ph"] == "M":
            if ev["name"] == "thread_name":
                meta_tids.add(ev["tid"])
            continue
        assert ev["ts"] >= 0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
            per_track.setdefault(ev["tid"], []).append(ev["ts"])
    for tid, ts in per_track.items():
        assert ts == sorted(ts), f"track {tid} timestamps not monotonic"
    assert set(per_track) <= meta_tids, "track without thread_name metadata"
    return evs


def _tid(evs, track):
    return next(ev["tid"] for ev in evs if ev["ph"] == "M"
                and ev["name"] == "thread_name"
                and ev["args"]["name"] == track)


def test_tracer_chrome_format(tmp_path):
    tr = Tracer()
    t0 = clock_us()
    with span("engine", "admit", tr, prompt_len=16):
        pass
    tr.complete("prefill-chunk", "req0", t0, {"k_steps": 8})
    tr.counter("tokens", {"emitted": 5})
    with span("engine", "admit", tr):
        pass
    with span("engine", "dispatch"):            # no tracer: no event
        pass
    tr.complete("prefill-chunk", "req1", clock_us())
    evs = _check_chrome_trace(tr.to_dict())
    assert sum(ev["ph"] == "X" for ev in evs) == 4
    assert sum(ev["ph"] == "C" for ev in evs) == 1
    # the two admission spans share one track, prefill chunks another
    tracks = {ev["args"]["name"] for ev in evs
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert tracks == {"engine", "prefill-chunk"}
    admits = [ev for ev in evs if ev.get("name") == "admit"]
    assert [ev.get("args") for ev in admits] == [{"prompt_len": 16}, None]
    path = tmp_path / "trace.json"
    tr.save(path)
    assert json.loads(path.read_text()) == tr.to_dict()


# ---------------------------------------------------------------------------
# Engine integration: counters surface, conservation, zero-cost contract
# ---------------------------------------------------------------------------

def test_serve_metrics_free_on_hot_path(tmp_path):
    """Metrics+tracer on vs off: identical tokens, identical host syncs,
    identical jit cache sizes — and the device counters balance."""
    cfg, model, params, spec = _setup()
    common = sample_batch(jax.random.PRNGKey(7), spec, 1, 16)[0]
    import jax.numpy as jnp
    prompts = [jnp.concatenate(
        [common, sample_batch(jax.random.PRNGKey(50 + i), spec, 1, 8)[0]])
        for i in range(4)]
    gen = 6

    def mk(**kw):
        return Engine(model, params, slots=2, cache_len=48, k_steps=4,
                      block_size=8, prefix_cache=True,
                      check_invariants=True, **kw)

    reg, tr = MetricsRegistry(), Tracer()
    e_on = mk(metrics=reg, tracer=tr)
    e_off = mk()
    outs_on, st_on = e_on.serve(prompts, gen_tokens=gen, return_stats=True)
    outs_off, st_off = e_off.serve(prompts, gen_tokens=gen,
                                   return_stats=True)
    assert outs_on == outs_off
    assert st_on["host_syncs"] == st_off["host_syncs"]
    assert e_on.compile_counts() == e_off.compile_counts()

    # device counters: surfaced, identical on/off, and conserved
    c = st_on["counters"]
    assert set(c) == set(COUNTER_KEYS)
    assert c == st_off["counters"]
    # chunked path: every token emits through the dispatch grid
    assert c["tokens"] == st_on["tokens"]
    assert c["chunks_completed"] == len(prompts)
    assert c["prefix_hit_tokens"] == st_on["prefix_hits"]
    # popped == released + live; after drain only index holds live
    assert (c["blocks_popped"] - c["blocks_released"]
            == len(e_on._hold_blocks))
    assert c["drafted"] == c["accepted"] + c["rejected"] == 0  # no spec

    # lifecycle metrics landed with the required fields
    snap = reg.snapshot()
    assert snap["counters"]["requests.completed"] == len(prompts)
    assert snap["counters"]["device.tokens"] == c["tokens"]
    for h in ("request.ttft_s", "request.tpot_s", "request.queue_wait_s",
              "request.prompt_len", "request.gen_len",
              "request.prefix_hit_frac"):
        assert snap["histograms"][h]["count"] == len(prompts)
        assert snap["histograms"][h]["p50"] is not None
    for g in ("alloc.live_blocks", "alloc.free_blocks",
              "alloc.index_holds", "alloc.ledger_headroom"):
        assert snap["gauges"][g] is not None
    assert snap["gauges"]["alloc.live_blocks"] == len(e_on._hold_blocks)
    assert "spec.acceptance_rate" not in snap["gauges"]  # non-spec run

    # the engine-produced trace is schema-valid with the engine tracks
    evs = _check_chrome_trace(tr.to_dict())
    tracks = {ev["args"]["name"] for ev in evs
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert {"engine", "prefill-chunk"} <= tracks
    spans = {ev["name"] for ev in evs if ev["ph"] == "X"
             and ev["tid"] == _tid(evs, "engine")}
    assert {"admit", "dispatch", "sync", "host"} <= spans
    assert any(ev["ph"] == "C" for ev in evs)

    # warm second serve: counters re-zero, conservation re-baselines on
    # the now-held index blocks
    outs2, st2 = e_on.serve(prompts, gen_tokens=gen, return_stats=True)
    assert outs2 == outs_on                          # warm token exactness
    c2 = st2["counters"]
    assert (c2["blocks_popped"] - c2["blocks_released"]
            + st_on["counters"]["blocks_popped"]
            - st_on["counters"]["blocks_released"]
            == len(e_on._hold_blocks))
    assert c2["prefix_hit_tokens"] == st2["prefix_hits"]
    assert c2["prefix_hit_tokens"] > c["prefix_hit_tokens"]  # warm hits


def test_kv_rows_read_counts_what_decode_attention_reads(monkeypatch):
    """``kv_rows_read``: the whole table (``MB * bs`` a slot) every decode
    step on the gather path; on the kernel path (run here in interpret
    mode) each slot's blocks up to its length — for one slot whose prompt
    of ``L`` rows lands in the chunk of scan step 0 (after that step's
    decode sub-step, which reads nothing), ``ceil((L + i - 1) / bs) * bs``
    at step ``i >= 1`` — in every attention layer.  Both paths serve the
    same tokens."""
    import dataclasses
    import jax.numpy as jnp
    from repro.kernels.paged_attention import ops
    from repro.models.lm import decode_rows_read

    cfg = dataclasses.replace(reduced(get_arch("glm4-9b")), head_dim=128)
    model = build_model(cfg)
    params = model.init(KEY)
    spec = LanguageSpec(vocab=cfg.vocab_size)
    P, gen, bs, K = 21, 7, 16, 4
    prompt = sample_batch(jax.random.PRNGKey(3), spec, 1, P)[0]

    def serve():
        eng = Engine(model, params, slots=1, cache_len=64, k_steps=K,
                     block_size=bs)
        return eng.serve([prompt], gen_tokens=gen, return_stats=True)

    outs, st = serve()
    MB, steps = 64 // bs, st["decode_steps"]
    assert st["counters"]["kv_rows_read"] == steps * MB * bs * cfg.n_layers

    monkeypatch.setattr(ops, "KERNEL_PLATFORMS", ("tpu", "cpu"))
    outs_k, st_k = serve()
    assert outs_k == outs
    L = int(prompt.shape[0])
    want = sum(-(-(L + i - 1) // bs) * bs
               for i in range(1, steps)) * cfg.n_layers
    assert st_k["decode_steps"] == steps
    assert st_k["counters"]["kv_rows_read"] == want

    # the step's count from a cache: rounded lengths, or the whole table
    pk = jnp.zeros((cfg.n_layers, 9, bs, cfg.n_kv_heads, 128), jnp.bfloat16)
    cache = {"lengths": jnp.asarray([0, 1, 16, 23], jnp.int32),
             "tbl": jnp.zeros((4, MB), jnp.int32),
             "stack": {"L0": {"pk": pk, "pv": pk}}}
    assert int(decode_rows_read(cache, cfg)) == (0 + 16 + 16 + 32) * \
        cfg.n_layers
    monkeypatch.setattr(ops, "KERNEL_PLATFORMS", ("tpu",))
    assert int(decode_rows_read(cache, cfg)) == 4 * MB * bs * cfg.n_layers
