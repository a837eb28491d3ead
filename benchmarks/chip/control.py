#!/usr/bin/env python3
"""Read a cell's correctness numbers for the program and for its control.

  python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
      [--seconds 1]

For each seed, in one process: the cell's set-up and a short window at its
own load (one serve call, or one quantize), then the numbers that decide
``correct`` for what the program produced, and the same numbers for the
control, the reference one precision step down in the program's place
(Alg. 1 in bfloat16; the forward with float8 e4m3 matmul inputs), and for
DAQ also with the search skipped (alpha = 1, ``no_search``).  Prints one
JSON line per seed.  The benchmark's own runs never run the control;
the limits in ``limits/`` are set between these two readings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 2
    from benchmarks.chip import timed
    from benchmarks.chip.model import load_config
    from benchmarks.chip.run import cell_files

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    f = cell_files(ROOT, bench, args.workload)
    cfg = load_config(f["config"])
    mix = json.loads(f["traffic"].read_text())
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        d = timed.load(f["dir"], mix["timed"])(cfg, mix, seed, f["dir"])
        d.setup()
        d.window(args.seconds)
        d.free()
        program = d.check()
        control = d.controls()
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program, "control": control,
                          "seconds": time.perf_counter() - t}), flush=True)
        d = None            # free this seed's arrays first
    return 0


if __name__ == "__main__":
    sys.exit(main())
