#!/usr/bin/env python3
"""One cell's traced window, read by program span and named scope.

  python3 benchmarks/chip/spans.py --workload <cell> --seed <n> \
      --seconds <s> [--out <file.json>]

Runs the cell as ``run.py --trace 1`` does (set-up, one traced window, the
check against the reference, the readers of the cell's per-layer metrics)
and reads the same trace for the per-layer metrics of :data:`SPAN_METRICS`
besides.  Their readers ``metrics/<name>.py`` take the context ``run.py``
gives its readers, in which the reduced trace also holds ``idle_in``
(device idle inside each program span, ``span_reduce.idle_in``) and
``device_scopes`` (device time per named scope,
``span_reduce.device_scopes``, its map from ``Engine.hlo_texts()``), and
the work also holds ``host_reads`` (``QuantReport.host_reads`` of one
``quantize()`` call).  A reader that finds nothing to read gives nothing.

Prints one JSON object and writes it to ``--out``: ``correct`` and the
checks, the end-to-end readings of the traced window, every metric, the
device idle per span, the device time per scope, the longest ops with
their scope, the longest idle gaps, and ``unmapped_s``: the self time of
ops of a dispatch module that its compiled text does not hold (nonzero
means the map was read from another executable).  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

SPAN_METRICS = ("host_idle.serve", "prefill_share.serve",
                "qlinear_share.serve", "attn_share.serve",
                "report_idle.quant", "host_reads.quant")


def scope_map(path) -> dict:
    """``{"<module>:<op>": scope path}`` of every instruction of the
    engine's dispatches that ran ("" under no scope); ``{}`` without an
    engine."""
    engine = getattr(path, "engine", None)
    if engine is None:
        return {}
    from repro.analysis.hlo import HloModule
    from repro.telemetry.scopes import op_scopes
    smap = {}
    for mod, text in engine.hlo_texts().items():
        scoped = op_scopes(text)
        smap.update((f"{mod}:{ins.name}", scoped.get(ins.name, ""))
                    for instrs in HloModule(text).computations.values()
                    for ins in instrs)
    return smap


def read_cell(root: Path, bench: dict, name: str, seed: int,
              seconds: float, devices) -> dict:
    """Set up, run one traced window, check, read; the result as a dict."""
    import jax

    from benchmarks.chip import span_reduce, timed, trace_reduce
    from benchmarks.chip.model import load_config
    from benchmarks.chip.peaks import peaks
    from benchmarks.chip.run import cell_files, peak_bytes

    f = cell_files(root, bench, name)
    cfg = load_config(f["config"])
    mix = json.loads(f["traffic"].read_text())
    limits = json.loads(f["limits"].read_text())
    path = timed.load(f["dir"], mix["timed"])(cfg, mix, seed, f["dir"])
    path.setup()
    setup_s = time.perf_counter() - T0

    tdir = tempfile.mkdtemp(prefix="chipbench_spans_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        path.window(seconds)
    jax.profiler.stop_trace()
    e2e = {**path.end_to_end(peak_bytes(devices)), "setup_s": setup_s}
    ctx = {"work": path.work(), "window_s": path.window_s,
           "peaks": peaks(devices[0].device_kind)}
    report = getattr(path, "report", None)
    if report is not None:
        ctx["work"]["host_reads"] = report.host_reads
    smap = scope_map(path)
    path.free()
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in path.check().items()}

    files = sorted(Path(tdir).rglob("*.xplane.pb"))
    devs, host = trace_reduce.load(files[-1]) if files else ({}, {})
    shutil.rmtree(tdir, ignore_errors=True)
    reduced = trace_reduce.reduce(devs, host, top=15)
    if reduced:
        reduced.update(idle_in=span_reduce.idle_in(devs, host),
                       device_scopes=span_reduce.device_scopes(devs, host,
                                                               smap))
    ctx["trace"] = reduced
    metrics = {}
    for metric in [m["name"] for m in f["per_layer"]] + list(SPAN_METRICS):
        v = timed.load_file(f["dir"] / "metrics" / f"{metric}.py",
                            "read")(ctx)
        if v is not None:
            metrics[metric] = v
    mods = {op.split(":", 1)[0] for op in smap}
    return {
        "workload": name, "seed": seed, "device": devices[0].device_kind,
        "correct": path.failed == 0 and all(c["value"] <= c["limit"]
                                            for c in checks.values()),
        "end_to_end": e2e, "metrics": metrics,
        "busy_s": reduced.get("busy_s"), "window_s": reduced.get("window_s"),
        "idle_in": reduced.get("idle_in"),
        "device_scopes": reduced.get("device_scopes"),
        "device_ops": [[n, s, smap.get(n, "")]
                       for n, s in reduced.get("device_ops", [])],
        "idle_gaps": reduced.get("idle_gaps"),
        "mapped_ops": len(smap),
        "unmapped_s": sum(s for op, s in
                          span_reduce.self_seconds(devs, host).items()
                          if op.split(":", 1)[0] in mods and op not in smap),
        "checks": checks,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = next(w["chips"] for w in bench["workloads"]
                 if w["name"] == args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from benchmarks.chip.peaks import peaks
    from repro.runtime import enable_compile_cache
    enable_compile_cache()

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"spans.py: cell {args.workload} needs {chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    peaks(devices[0].device_kind)
    res = read_cell(ROOT, bench, args.workload, args.seed, args.seconds,
                    devices[:chips])
    line = json.dumps(res)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
