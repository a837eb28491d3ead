"""Named scopes of the program's device work, read back from compiled HLO.

The model and the engine wrap their device work in ``jax.named_scope``:

* ``qlinear``       — a quantized linear (``quant_runtime.qlinear``):
                      dequantize + matmul, the tied head, the embedding take;
* ``attention``     — the attention core only (paged decode / prefill /
                      verify attention, decode attention), not the
                      projections around it, so no op is under both;
* ``mamba``         — the Mamba-2 core (``models.ssm``): causal conv, SSD
                      chunk scan or per-token recurrence, gated norm; not
                      its projections;
* ``moe``           — the dropless expert layer (``models.moe``): routing,
                      the sort, the grouped expert matmuls and the combine;
                      not the shared expert (a ``qlinear`` MLP);
* ``prefill_chunk`` — the in-scan chunked-prefill piece
                      (``scheduler.chunk_prefill_substep``);
* ``keep_rows``     — the per-slot cache merge (``scheduler._keep_rows``);
* ``sample``        — token sampling (``engine.sampler.sample``);
* ``paged_attn``    — the paged attention kernel
                      (``kernels.paged_attention``), nested in
                      ``attention``: decode and verify on a TPU;
* ``mamba_state``   — the Mamba-2 decode-step kernel
                      (``kernels.ssm_decode``), nested in ``mamba``:
                      decode on a TPU.

A scope is metadata: it lands in each HLO instruction's ``op_name`` and
leaves the compiled code as it was.  A device trace carries no scope (its
op events name only the module and the instruction), so the scope of a
device op is read from the compiled module: :func:`op_scopes` maps each
instruction to the scopes on its ``op_name`` path.  A fusion carries its
root's ``op_name``, so every op has one path.
"""
from __future__ import annotations

from repro.analysis.hlo import HloModule

SCOPES = ("qlinear", "attention", "mamba", "moe", "prefill_chunk",
          "keep_rows", "sample", "paged_attn", "mamba_state")

_OP_NAME = r'op_name="([^"]*)"'


def scope_path(op_name: str) -> str:
    """The scopes of :data:`SCOPES` on an ``op_name`` path, outermost
    first, joined by ``/`` (a scope nested in itself counts once)."""
    out: list[str] = []
    for part in op_name.split("/"):
        if part in SCOPES and part not in out:
            out.append(part)
    return "/".join(out)


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: scope path}`` for every instruction of a
    compiled module whose ``op_name`` passes through a scope of
    :data:`SCOPES`; instructions under none are left out."""
    out = {}
    for instrs in HloModule(hlo_text).computations.values():
        for ins in instrs:
            path = scope_path(ins.attr(_OP_NAME) or "")
            if path:
                out[ins.name] = path
    return out
