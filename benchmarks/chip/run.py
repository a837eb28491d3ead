#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file, its traffic mix ``traffic/<mix>.json`` (whose
``timed`` names the timed path ``kinds/<timed>.py``, and whose length
distributions are ``dists/<dist>.py``), the limits of its correctness
check ``limits/<cell>.json``, and each per-layer metric's reader
``metrics/<metric>.py``.  The last line of stdout is one JSON
object; the numbers compared for ``correct`` are also the last lines of
stderr.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
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
REL = HERE.relative_to(ROOT)
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def cell_files(root: Path, bench: dict, name: str) -> dict:
    """The cell's entries and files, all found by name."""
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    here = root / REL
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name]) and m["moves"] in names]
    return {"cell": cell, "config": root / conf["file"], "dir": here,
            "traffic": here / "traffic" / f"{cell['traffic']}.json",
            "limits": here / "limits" / f"{name}.json",
            "end_to_end": e2e, "per_layer": per_layer,
            "readers": {m["name"]: here / "metrics" / f"{m['name']}.py"
                        for m in per_layer}}


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def run_cell(root: Path, bench: dict, name: str, seed: int, seconds: float,
             trace: bool, devices) -> dict:
    """Set up, measure, check; returns the result line as a dict."""
    import jax

    from benchmarks.chip import timed, trace_reduce
    from benchmarks.chip.model import load_config

    f = cell_files(root, bench, name)
    cfg = load_config(f["config"])
    mix = json.loads(f["traffic"].read_text())
    limits = json.loads(f["limits"].read_text())
    path = timed.load(f["dir"], mix["timed"])(cfg, mix, seed, f["dir"])
    path.setup()
    setup_s = time.perf_counter() - T0

    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    if trace:
        # Python frames and HLO protos would swell the trace tenfold and
        # slow the host loop under measurement; annotations stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        path.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes(devices)}
    e2e = path.end_to_end(device["memory_peak_bytes"])
    e2e["setup_s"] = setup_s
    work = path.work()
    path.free()
    t = time.perf_counter()
    numbers = path.check()
    print(f"check in {time.perf_counter() - t:.1f} s: {numbers}",
          file=sys.stderr, flush=True)

    out = {}
    if trace:
        from benchmarks.chip.peaks import peaks
        files = sorted(Path(tdir).rglob("*.xplane.pb"))
        t = time.perf_counter()
        reduced = trace_reduce.reduce(*trace_reduce.load(files[-1])) \
            if files else {}
        print(f"trace of {files[-1].stat().st_size if files else 0} bytes "
              f"reduced in {time.perf_counter() - t:.1f} s", file=sys.stderr)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = {"trace": reduced, "work": work, "window_s": path.window_s,
               "peaks": peaks(device["kind"])}
        metrics = {}
        for m in f["per_layer"]:
            v = timed.load_file(f["readers"][m["name"]], "read")(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in f["end_to_end"] if m["name"] in e2e}
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = path.failed == 0 and all(c["value"] <= c["limit"]
                                         for c in checks.values())
    return {"correct": correct, "attempted": path.attempted,
            "failed": path.failed, "metrics": metrics, "device": device,
            **out, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
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
        print(f"run.py: cell {args.workload} needs {chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    peaks(devices[0].device_kind)
    res = run_cell(ROOT, bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), devices[:chips])
    for k, c in res["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
