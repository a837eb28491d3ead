#!/usr/bin/env python3
"""Record the small profiler trace that ``test_chipbench_trace.py`` reduces.

  python3 benchmarks/chip/tests/record_trace.py <out_dir>

On the chip: inside a host span named ``window``, two bursts of matmuls with
a 50 ms host sleep (span ``host_sleep``) between them.  Writes
``<out_dir>/small.xplane.pb`` and prints each plane and line of the trace
with its event count.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out: Path) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(f(x))
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(20):
            x = f(x)
        jax.block_until_ready(x)
        with jax.profiler.TraceAnnotation("host_sleep"):
            time.sleep(0.05)
        for _ in range(20):
            x = f(x)
        jax.block_until_ready(x)
    jax.profiler.stop_trace()
    src = sorted(Path(tdir).rglob("*.xplane.pb"))[-1]
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "small.xplane.pb")
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(str(src)).planes:
        print(plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  {line.name}: {len(evs)} events, first "
                  f"{[(e.name, e.start_ns, e.duration_ns) for e in evs[:2]]}")
    shutil.rmtree(tdir)
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
