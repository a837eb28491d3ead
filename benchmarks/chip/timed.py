"""What every timed path shares, and how the harness finds one.

A traffic mix names its timed path with ``"timed"``; the harness loads the
class ``Timed`` from ``kinds/<name>.py``.  ``Timed(cfg, mix, seed, here)``
builds the system under test in ``setup`` (counted in ``setup_s``), runs
it in ``window(seconds)``, reports ``end_to_end(peak_bytes)`` and the
``work()`` that the per-layer readers take, frees the program's state in
``free``, and then compares what the window produced with the plain
reference in ``check``; ``controls`` gives the same numbers for the
controls, for ``control.py``.
"""
from __future__ import annotations

import importlib.util
import sys
import time
from pathlib import Path

import jax


def load_file(path: Path, attr: str):
    """``attr`` of the Python file ``path``, loaded once per path."""
    path = Path(path).resolve()
    name = f"chipbench:{path}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return getattr(sys.modules[name], attr)


def load(here: Path, kind: str):
    """The ``Timed`` class of ``kinds/<kind>.py`` under ``here``."""
    return load_file(Path(here) / "kinds" / f"{kind}.py", "Timed")


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def log(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)
