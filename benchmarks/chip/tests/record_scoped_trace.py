#!/usr/bin/env python3
"""Record the small profiler trace that ``test_chipbench_spans.py`` reads.

  python3 benchmarks/chip/tests/record_scoped_trace.py <out_dir>

On the chip, inside a host span named ``window``: ten bf16 [2048, 2048]
matmuls under the named scope ``qlinear`` (module ``jit_scoped``), each
followed by an elementwise op under no scope (module ``jit_plain``), then
the program span ``engine.host`` over a 50 ms host sleep, then ten more of
each.  Writes ``<out_dir>/scoped.xplane.pb`` and, from the two compiled
modules, ``<out_dir>/scoped.scopes.json``: the ``{"<module>:<op>": scope
path}`` map of ``repro.telemetry.scopes.op_scopes`` and the list of every
``"<module>:<op>"`` of the two modules.
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))


def scoped(x):
    with jax.named_scope("qlinear"):
        return x @ x


def plain(x):
    return jnp.tanh(x) * 0.5


def main(out: Path) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 2
    from repro.analysis.hlo import HloModule
    from repro.telemetry.scopes import op_scopes
    from repro.telemetry.trace import span
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    fns = [jax.jit(scoped), jax.jit(plain)]
    smap, names = {}, []
    for fn in fns:
        text = fn.lower(x).compile().as_text()
        mod = text.split(",", 1)[0].split()[-1]
        smap.update((f"{mod}:{op}", sc) for op, sc in op_scopes(text).items())
        names += [f"{mod}:{ins.name}" for instrs in
                  HloModule(text).computations.values() for ins in instrs]
    f, g = fns
    jax.block_until_ready(g(f(x)))

    def burst(x):
        for _ in range(10):
            x = g(f(x))
        return jax.block_until_ready(x)

    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("window"):
        x = burst(x)
        with span("engine", "host"):
            time.sleep(0.05)
        x = burst(x)
    jax.profiler.stop_trace()
    src = sorted(Path(tdir).rglob("*.xplane.pb"))[-1]
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "scoped.xplane.pb")
    (out / "scoped.scopes.json").write_text(json.dumps(
        {"scopes": smap, "ops": sorted(names)}, indent=1) + "\n")
    shutil.rmtree(tdir)
    print(json.dumps(smap))
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
