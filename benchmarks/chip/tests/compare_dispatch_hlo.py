#!/usr/bin/env python3
"""Compare the compiled decode and chunk dispatches of two checkouts.

  python3 benchmarks/chip/tests/compare_dispatch_hlo.py <other checkout> \
      [--layers 2] [--out <dir>]

Builds the engine of the ``glm4-9b.decode`` cell (its configuration and
traffic, ``--layers`` layers, fp8 storage weights at channel granularity)
from shapes alone, in each checkout in a process of its own, and compiles
the ``_dispatch`` and ``_dispatch_chunk`` entry points for a described TPU
v5e: no chip is needed.  Each compiled module's text is then reduced to
its code: the module name, the op metadata (``op_name``, source
locations) and the tables of file and function names go, and every
instruction and computation name is renumbered in order of first use.
Prints, per entry point, whether the two reduced texts are identical and
how many lines of each carry metadata; exits 1 when any differs.  With
``--out`` it writes the raw and the reduced texts there.
"""
from __future__ import annotations

import argparse
import json
import re
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
ENTRIES = ("_dispatch", "_dispatch_chunk")
TOPOLOGY = "v5e:2x2"


def emit(root: Path, out: Path, layers: int) -> None:
    """Compile the entries of ``root`` and write ``<entry>.txt`` to
    ``out``; runs in a process whose imports come from ``root``."""
    sys.path[:0] = [str(root / "src"), str(root)]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.chip import model as M
    from repro.core.policy import path_str, should_quantize
    from repro.engine import Engine
    from repro.engine.scheduler import init_slot_state
    from repro.models import build_model
    from repro.quant_runtime.qparams import QuantizedTensor

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)
    shd = SingleDeviceSharding(topo.devices[0])
    chip = root / "benchmarks" / "chip"
    cfg = M.load_config(chip / "configs" / "glm4-9b.json")
    mix = json.loads((chip / "traffic" / "decode.json").read_text())
    model = build_model(M.model_config(cfg, layers))

    def sds(x, dtype=None):
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=shd)

    def fp8(path, leaf):
        if not should_quantize(path_str(path), leaf, M.SKIP):
            return sds(leaf)
        scale = jax.ShapeDtypeStruct(leaf.shape[:-2] + (1, leaf.shape[-1]),
                                     jnp.float32, sharding=shd)
        return QuantizedTensor(data=sds(leaf, jnp.float8_e4m3fn),
                               scale=scale, fmt="fp8_e4m3",
                               granularity="channel", block_size=128,
                               out_dtype="bfloat16")

    params = jax.tree_util.tree_map_with_path(
        fp8, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    eng = Engine(model, params, slots=mix["slots"],
                 cache_len=mix["cache_len"], num_blocks=mix["num_blocks"],
                 **cfg["engine"])
    ec = eng.cfg
    cache = jax.tree.map(sds, jax.eval_shape(
        lambda: model.init_paged_cache(ec.slots, ec.cache_len,
                                       block_size=ec.block_size,
                                       num_blocks=eng._num_blocks)))
    state = jax.tree.map(sds, jax.eval_shape(
        lambda: init_slot_state(ec.slots, prompt_cap=ec.cache_len)))
    key = sds(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    for name in ENTRIES:
        fn = eng.entry_points()[name]["fn"]
        text = fn.lower(params, state, cache, key).compile().as_text()
        (out / f"{name}.txt").write_text(text)


_METADATA = re.compile(r", metadata=\{[^{}]*\}")
_NAME = re.compile(r"(?<![\w.%-])%?[A-Za-z_][\w-]*(?:\.[\w-]+)+")


def reduce_text(text: str) -> str:
    """The module's code: no name, metadata or name tables, and every
    dotted name renumbered in order of first use."""
    lines = text.splitlines()
    head = re.sub(r"^HloModule [\w.-]+", "HloModule m", lines[0])
    start = next(i for i, ln in enumerate(lines)
                 if ln.startswith(("%", "ENTRY")))
    code = _METADATA.sub("", "\n".join([head] + lines[start:]))
    names: dict[str, str] = {}

    def rename(m):
        tok = m.group(0)
        bare = tok.lstrip("%")
        return tok[:len(tok) - len(bare)] + names.setdefault(
            bare, f"n{len(names)}")
    return _NAME.sub(rename, code) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("other", type=Path, nargs="?")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--out", type=Path)
    ap.add_argument("--emit", type=Path, nargs=2, metavar=("ROOT", "OUT"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.emit:
        emit(args.emit[0].resolve(), args.emit[1], args.layers)
        return 0
    if args.other is None:
        ap.error("give the other checkout")

    out = args.out or Path(tempfile.mkdtemp(prefix="dispatch_hlo_"))
    texts = {}
    for side, root in (("other", args.other), ("this", ROOT)):
        d = out / side
        d.mkdir(parents=True, exist_ok=True)
        subprocess.run([sys.executable, __file__, "--layers",
                        str(args.layers), "--emit", str(root), str(d)],
                       check=True)
        texts[side] = {n: (d / f"{n}.txt").read_text() for n in ENTRIES}
    same = True
    for name in ENTRIES:
        a, b = (reduce_text(texts[s][name]) for s in ("other", "this"))
        if args.out:
            for s, t in (("other", a), ("this", b)):
                (out / s / f"{name}.reduced.txt").write_text(t)
        mods = [texts[s][name].split(",", 1)[0].split()[-1]
                for s in ("other", "this")]
        meta = [texts[s][name].count("metadata={") for s in ("other", "this")]
        print(f"{name}: modules {mods[0]} / {mods[1]}, lines with metadata "
              f"{meta[0]} / {meta[1]}, {a.count(chr(10))} lines of code: "
              f"{'identical' if a == b else 'DIFFERENT'}")
        same &= a == b
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
