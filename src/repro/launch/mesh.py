"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization, and tests/benches must keep seeing 1 device.

Axis semantics:
  pod    -- spans ICI-disconnected pods (DCN); pure data parallelism.
  data   -- intra-pod data parallel + FSDP (ZeRO-3 parameter sharding).
  model  -- tensor/expert parallel.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, model: int = 1):
    """A mesh over whatever devices exist (tests, CPU examples, one host's
    chips)."""
    n = len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_info(mesh) -> dict:
    return {
        "axis_names": list(mesh.axis_names),
        "shape": [int(mesh.devices.shape[i]) for i in range(mesh.devices.ndim)],
        "n_devices": int(np.prod(mesh.devices.shape)),
    }


def dp_axes(mesh) -> tuple[str, ...]:
    """Axes that carry the batch (pod is an outer data axis)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def dp_size(mesh) -> int:
    s = 1
    for a in dp_axes(mesh):
        s *= mesh.shape[a]
    return s


def model_size(mesh) -> int:
    return mesh.shape["model"]
