"""Device idle inside program spans and device time under named scopes
(``span_reduce``), on hand-built events and on a trace recorded on a TPU
v5e by ``record_scoped_trace.py``: twenty bf16 [2048, 2048] matmuls under
the scope ``qlinear``, each followed by an unscoped elementwise op, with
the program span ``engine.host`` over a 50 ms host sleep half way.  In
that trace the first ops start about a millisecond before the host span
``window`` that dispatched them: the device clock is that far off the
host's, and the window clips them."""
import json
import os
import subprocess
import sys

import pytest

import chipbench_tiny
from benchmarks.chip import peaks, timed
from benchmarks.chip import span_reduce as S
from benchmarks.chip import trace_reduce as T

DATA = chipbench_tiny.CHIP / "testdata"

# one device, ops at [0, 10) and [20, 30) ns of a [0, 40) window; a second
# device idle throughout
HOST = {"main": [("window", 0, 40), ("engine.sync", 5, 25),
                 ("engine.host", 25, 35), ("engine.host", 38, 45),
                 ("serve_call", 0, 40), ("np.asarray", 12, 14)],
        "other": [("engine.admit", 10, 20)]}
DEVICES = {"d0": [("m:a", 0, 10), ("m:b", 20, 30)], "d1": []}


def test_idle_in_intersects_idle_with_each_span():
    got = S.idle_in(DEVICES, HOST)
    # d0 idle [10, 20) and [30, 40); d1 idle [0, 40); averaged over both
    assert got == pytest.approx({
        "engine.sync": (10 + 20) / 2 / 1e9,
        "engine.host": ((5 + 2) + (10 + 2)) / 2 / 1e9,
        "engine.*": ((10 + 7) + (30 + 2)) / 2 / 1e9})
    # other threads, harness spans and library spans are not the program's
    assert set(got) == {"engine.sync", "engine.host", "engine.*"}


def test_idle_in_is_bounded_by_the_idle_time():
    busy = T.reduce(DEVICES, HOST)
    idle = busy["window_s"] - busy["busy_s"]
    assert S.idle_in(DEVICES, HOST)["engine.*"] <= idle


def test_device_scopes_count_self_time_on_every_scope_of_the_path():
    devices = {"d0": [("m:while", 0, 100), ("m:dot", 10, 40),
                      ("m:exp", 50, 60), ("m:copy", 70, 80)]}
    smap = {"m:dot": "prefill_chunk/qlinear", "m:exp": "attention",
            "m:while": "prefill_chunk"}
    got = S.device_scopes(devices, {"main": [("window", 0, 100)]}, smap)
    # the while's self time (100 - 30 - 10 - 10) counts once; copy: none
    assert got == pytest.approx({"prefill_chunk": (50 + 30) / 1e9,
                                 "qlinear": 30 / 1e9, "attention": 10 / 1e9})


def test_overlap_of_interval_lists():
    assert S.overlap([(0, 5), (8, 12)], [(3, 9), (11, 20)]) == 2 + 1 + 1
    assert S.overlap([], [(0, 1)]) == 0


def _read(name, ctx):
    return timed.load_file(chipbench_tiny.CHIP / "metrics" / f"{name}.py",
                           "read")(ctx)


def test_span_readers_read_the_reductions():
    smap = {"m:a": "prefill_chunk/qlinear", "m:b": "attention"}
    trace = T.reduce(DEVICES, HOST)
    trace.update(idle_in=S.idle_in(DEVICES, HOST),
                 device_scopes=S.device_scopes(DEVICES, HOST, smap))
    ctx = {"trace": trace, "work": {"host_reads": 197}}
    # busy 10 ns of a 40 ns window (averaged over d0 and idle d1)
    assert trace["busy_s"] == pytest.approx(10e-9)
    assert _read("host_idle.serve", ctx) == pytest.approx(
        100 * 24.5 / 40)
    assert _read("prefill_share.serve", ctx) == pytest.approx(50)
    assert _read("qlinear_share.serve", ctx) == pytest.approx(50)
    assert _read("attn_share.serve", ctx) == pytest.approx(50)
    assert _read("host_reads.quant", ctx) == 197
    assert _read("report_idle.quant", ctx) is None
    host = {"main": [("window", 0, 40), ("quantize.report", 5, 25)]}
    ctx["trace"] = dict(trace, idle_in=S.idle_in(DEVICES, host))
    assert _read("report_idle.quant", ctx) == pytest.approx(100 * 15 / 40)
    assert _read("host_idle.serve", ctx) is None


@pytest.mark.parametrize("name", ["host_idle.serve", "prefill_share.serve",
                                  "qlinear_share.serve", "attn_share.serve",
                                  "report_idle.quant", "host_reads.quant"])
def test_span_readers_find_nothing_in_a_program_without_spans(name):
    """A trace reduced without spans or scopes, and work without the
    counter, as from a program that has none: no reading, no error."""
    ctx = {"trace": T.reduce(DEVICES, HOST), "work": {"min_bytes": 1}}
    assert _read(name, ctx) is None
    assert _read(name, {"trace": {}, "work": {}}) is None


@pytest.mark.parametrize("mix", ["decode", "daq"])
def test_read_cell_on_the_cpu(tmp_path, monkeypatch, mix):
    """The tiny cells end to end: checked, counted and mapped.  A CPU
    trace has no TPU plane, so the readers of the trace give nothing."""
    from benchmarks.chip import check
    from benchmarks.chip import model as M
    from benchmarks.chip.spans import read_cell
    from repro.core.metrics import PARTIAL_KEYS
    import jax
    root = chipbench_tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    res = read_cell(root, bench, f"tiny.{mix}", 3, 0.1, jax.devices()[:1])
    assert res["correct"]
    assert not any(k in res["metrics"] for k in (
        "host_idle.serve", "qlinear_share.serve", "report_idle.quant"))
    if mix == "decode":
        assert res["mapped_ops"] > 0
        assert "occupancy.serve" in res["metrics"]
        assert "host_reads.quant" not in res["metrics"]
    else:
        cfg = chipbench_tiny.TINY
        specs, _ = M.layout(M.model_config(cfg, cfg["distinct_layers"]))
        n = sum(check.eligible(p, s) for p, s, _, _ in specs)
        per_leaf = 2 * len(PARTIAL_KEYS) + 1 + 2 * 4
        assert res["metrics"]["host_reads.quant"] == n * per_leaf + 2 * 4
        assert res["mapped_ops"] == 0


def test_spans_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, str(chipbench_tiny.CHIP / "spans.py"),
                        "--workload", "glm4-9b.daq", "--seed", "1",
                        "--seconds", "1"], env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 2
    assert "needs 1 TPU chip" in r.stderr and not r.stdout


def test_nothing_to_read_gives_nothing():
    assert S.idle_in({}, HOST) == {}
    assert S.idle_in(DEVICES, {"main": [("engine.sync", 0, 1)]}) == {}
    assert S.device_scopes(DEVICES, {}, {}) == {}


@pytest.fixture(scope="module")
def scoped():
    devices, host = T.load(DATA / "scoped.xplane.pb")
    names = json.loads((DATA / "scoped.scopes.json").read_text())
    return devices, host, names


def test_scoped_trace_ops_are_the_compiled_instructions(scoped):
    devices, _, names = scoped
    ran = {n for evs in devices.values() for n, _, _ in evs}
    assert ran <= set(names["ops"])
    # the matmul's fusion carries the scope of its root; the convolution
    # inside it is no op of its own on the device
    assert ran & set(names["scopes"]) == {"jit_scoped:fusion"}


def test_scoped_trace_idle_in_the_sleep(scoped):
    devices, host, _ = scoped
    got = S.idle_in(devices, host)
    assert set(got) == {"engine.host", "engine.*"}
    assert got["engine.host"] == got["engine.*"]
    span = next(e - s for evs in host.values() for n, s, e in evs
                if n == "engine.host") / 1e9
    # the device stays idle through the 50 ms sleep; its clock sits about
    # a millisecond off the host's, so a little less than the span reads
    assert 0.049 <= got["engine.host"] <= span
    assert got["engine.host"] == pytest.approx(0.049855961, rel=1e-9)


def test_scoped_trace_device_time_under_qlinear(scoped):
    devices, host, names = scoped
    got = S.device_scopes(devices, host, names["scopes"])
    assert set(got) == {"qlinear"}
    assert got["qlinear"] == pytest.approx(0.001544371, rel=1e-9)
    busy = T.reduce(devices, host)["busy_s"]
    # the matmuls are most of the busy time; the unscoped tanh and the
    # copies XLA put in (no op_name, so no scope) are the rest
    assert 0.5 * busy < got["qlinear"] < busy
    matmuls = sum(e - s for evs in devices.values() for n, s, e in evs
                  if n == "jit_scoped:fusion") / 1e9
    # the window clips the matmuls that ran before it opened
    assert got["qlinear"] <= matmuls
