"""Pass 1: compile contracts for every jitted engine dispatch.

For each configuration in the matrix (model family x serving mode x mesh)
this pass builds a real :class:`~repro.engine.Engine`, takes its
entry-point registry (``Engine.entry_points()``), lowers **and compiles**
each entry on canonical example inputs, and checks declarative contracts
on the jaxpr-free artifacts — the compiled HLO text and the abstract
signatures:

* **donation-not-landed** — every donated cache/pool operand must appear
  in the compiled module's ``input_output_alias`` table.  A donation XLA
  could not use means the buffer is silently copied: 2x cache memory at
  every dispatch, invisible to every runtime parity test.  Reports the
  bytes wasted.
* **host-boundary** — no infeed/outfeed/send/recv and no python-callback
  custom-calls anywhere in a traced entry.  One of these inside the
  K-step scan reintroduces the per-token host sync the dispatch exists
  to amortize (~100x on the serve bench).
* **recompile-fingerprint** — the canonical abstract signature (tree
  paths + shapes/dtypes + static argument values) of each entry is hashed
  and pinned in a checked-in manifest.  Drift means the entry's jit cache
  key changed (a new state field, a dtype change, a weak-type literal) —
  exactly the edits that cause silent per-call recompiles at runtime.
  Runtime recompile *counts* are watched by the serve CLI telemetry.
* **weak-type-signature** — no example-input leaf may carry a weak type:
  a weak-typed scalar in the argument tree retraces against every strong
  dtype it meets.
* **f64 / cache-dtype-drift** — no f64 anywhere in compiled code, and the
  cache tree's leaf dtypes must round-trip the entry unchanged (a silent
  bf16 -> f32 upcast doubles pool bytes).
* **collective-manifest** — under a mesh, the set of collective kinds in
  the compiled module must match the manifest's expected set for that
  (config, entry): an unexpected all-gather under the scan is a silent
  per-step latency cliff.

The kernels triads (fp8_quant / fp8_matmul / scale_search jitted ops) run
the same host-boundary / f64 / fingerprint contracts (donation does not
apply — they consume live weights).
"""
from __future__ import annotations

import hashlib
import re
import warnings
from dataclasses import dataclass, field

from repro.staticcheck.report import Violation

DONATION_MIN_BYTES = 256   # ignore scalar-ish donated leaves (flag bytes
                           # that matter; lengths[B] etc. are noise)

_F64_RE = re.compile(r"\bf64\[")


@dataclass(frozen=True)
class Case:
    """One point of the config matrix.  Every engine serves through the
    paged pool with chunked prefill at the engine's default chunk."""
    name: str
    arch: str = "glm4-9b"          # dense; mixtral = SWA+MoE,
                                   # mamba2 = SSM, jamba = hybrid
    prefix: bool = False           # prefix cache
    spec: bool = False             # speculative decoding
    mesh: bool = False             # sharded over a 2-device host mesh
    cache_len: int = 32

    def engine_kwargs(self) -> dict:
        return dict(slots=2, cache_len=self.cache_len, k_steps=2,
                    block_size=8, prefix_cache=self.prefix,
                    n_spec=1 if self.spec else 0)


# The reduced matrix CI runs on every push: the dense stack through every
# serving mode, plus one mesh point.  The full matrix adds the other model
# families (SWA ring, MoE, SSM, hybrid) whose cache trees have different
# leaf sets and therefore different donation/dtype surfaces.  The
# speculative cases run both speculative entries (the pure rounds and the
# rounds + in-scan prefill phase) under the same donation / fingerprint /
# dtype contracts.
QUICK_MATRIX = (
    Case("dense-chunked"),
    Case("dense-prefix", prefix=True),
    Case("dense-spec-chunked", spec=True),
    Case("dense-chunked-mesh", mesh=True),
)
FULL_MATRIX = QUICK_MATRIX + (
    Case("swa-moe-chunked", arch="mixtral-8x22b"),
    Case("ssm-chunked", arch="mamba2-780m"),
    Case("ssm-spec-chunked", arch="mamba2-780m", spec=True),
    Case("hybrid-chunked", arch="jamba-v0.1-52b", cache_len=64),
    Case("dense-spec-prefix", spec=True, prefix=True),
)
MATRICES = {"quick": QUICK_MATRIX, "full": FULL_MATRIX}


def case_entry_names(case: Case) -> tuple[str, ...]:
    """The entries this configuration actually exercises at runtime."""
    if case.spec:
        return ("_dispatch_spec", "_dispatch_spec_chunk", "_admit_chunk",
                "_evict")
    return ("_dispatch", "_dispatch_chunk", "_admit_chunk", "_evict")


# -- engine + example-input construction ------------------------------------

def build_engine(case: Case):
    import jax

    from repro.configs import get_arch, reduced
    from repro.engine import Engine
    from repro.models import build_model

    cfg = reduced(get_arch(case.arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    draft = None
    if case.spec:
        from repro.configs import QuantConfig
        from repro.quantize import quantize
        qcfg = QuantConfig(method="absmax", granularity="channel")
        draft, _ = quantize(params, None, qcfg, mode="storage",
                            out_dtype="bfloat16")
    mesh = None
    if case.mesh:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=2)
    return Engine(model, params, mesh=mesh, draft_params=draft,
                  **case.engine_kwargs())


def entry_args(eng, case: Case, name: str) -> tuple:
    """Canonical example inputs matching the runtime call signature of one
    entry point (static arguments included, in position)."""
    import jax
    import jax.numpy as jnp

    from repro.engine.scheduler import init_slot_state

    cfg, model = eng.cfg, eng.model
    B = cfg.slots
    L = 8                       # canonical example prompt length
    key = jax.random.PRNGKey(0)
    cache = model.init_paged_cache(B, cfg.cache_len,
                                   block_size=cfg.block_size,
                                   num_blocks=eng._num_blocks)
    if eng.mesh is not None:
        cache = eng._place_cache(cache)
    pcap = cfg.cache_len
    state = init_slot_state(B, prompt_cap=pcap, moe=eng._moe)

    if name in ("_dispatch", "_dispatch_chunk"):
        return (eng.params, state, cache, key)
    if name in ("_dispatch_spec", "_dispatch_spec_chunk"):
        # depth is the runtime dynamic-speculation-depth operand: a strong
        # int32 scalar, so every value shares one traced signature (the
        # fingerprint contract pins that no depth move ever recompiles)
        return (eng.params, eng._draft_params, state, cache,
                jnp.int32(1), key)
    if name == "_admit_chunk":
        shared = jnp.full((eng._mb,), -1, jnp.int32)
        toks = jnp.zeros((pcap,), jnp.int32)
        i32 = jnp.int32
        return (cache, state, i32(0), toks, i32(L), shared, i32(0),
                i32(1), i32(0), i32(0), i32(0), i32(1))
    if name == "_evict":
        return (cache, state,
                jnp.full((eng._num_blocks,), -1, jnp.int32))
    raise KeyError(f"no example inputs for entry {name!r}")


# -- contract checks --------------------------------------------------------

def _abstractify(leaf):
    """Aval of a leaf, or None for non-array statics riding in a tree."""
    import jax

    try:
        return jax.api_util.shaped_abstractify(leaf)
    except (TypeError, ValueError):
        return None


def signature_fingerprint(args: tuple, static_argnums: tuple) -> str:
    """Stable hash of the abstract calling signature: flattened tree paths
    with shape/dtype/weak-type per dynamic leaf, plus static values."""
    import jax

    from repro.core.policy import path_str

    lines = []
    for i, a in enumerate(args):
        if i in static_argnums:
            lines.append(f"static[{i}]={a!r}")
            continue
        flat = jax.tree_util.tree_flatten_with_path(a)[0]
        for path, leaf in flat:
            aval = _abstractify(leaf)
            desc = (f"{aval.str_short()}{'*' if aval.weak_type else ''}"
                    if aval is not None else f"py:{leaf!r}")
            lines.append(f"arg[{i}]/{path_str(path)}:{desc}")
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]


def weak_type_leaves(args: tuple, static_argnums: tuple) -> list[str]:
    import jax

    from repro.core.policy import path_str

    out = []
    for i, a in enumerate(args):
        if i in static_argnums:
            continue
        for path, leaf in jax.tree_util.tree_flatten_with_path(a)[0]:
            aval = _abstractify(leaf)
            if aval is not None and aval.weak_type:
                out.append(f"arg[{i}]/{path_str(path)}")
    return out


def donated_leaf_params(args: tuple, donate: tuple,
                        static_argnums: tuple) -> list[tuple[int, str, int]]:
    """(entry param number, tree path, nbytes) of every donated leaf.
    Entry parameters of a jitted module are the flattened dynamic
    arguments in order."""
    import jax
    import numpy as np

    from repro.core.policy import path_str

    out = []
    p = 0
    dyn_index = -1
    for i, a in enumerate(args):
        if i in static_argnums:
            continue
        dyn_index += 1
        flat = jax.tree_util.tree_flatten_with_path(a)[0]
        for path, leaf in flat:
            if i in donate:
                aval = _abstractify(leaf)
                nbytes = int(np.prod(aval.shape, dtype=np.int64)
                             * aval.dtype.itemsize) if aval else 0
                out.append((p, f"arg[{i}]/{path_str(path)}", nbytes))
            p += 1
    return out


@dataclass
class EntryCheck:
    """Result of checking one (case, entry)."""
    violations: list[Violation] = field(default_factory=list)
    fingerprint: str = ""
    collectives: list[str] = field(default_factory=list)
    n_params: int = 0


def check_entry(case_name: str, entry_name: str, rec: dict, args: tuple,
                *, expect: dict | None, update: bool,
                mesh: bool = False, check_donation: bool = True,
                cache_in=None) -> EntryCheck:
    """Lower + compile one registered entry and run every contract."""
    import jax

    from repro.analysis.hlo import HloModule

    res = EntryCheck()
    where = f"{case_name}/{entry_name}"
    statics = rec.get("static_argnums", ())
    donate = rec.get("donate", ())

    # (c) recompile fingerprint + weak-type hygiene -------------------------
    res.fingerprint = signature_fingerprint(args, statics)
    for leaf in weak_type_leaves(args, statics):
        res.violations.append(Violation(
            kind="contract", rule="weak-type-signature", where=where,
            symbol=leaf,
            msg=f"{leaf} carries a weak type: the jit cache keys on weak "
                f"types, so this leaf retraces against every strong dtype "
                f"it meets"))

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # alias table is the truth source
        compiled = rec["fn"].lower(*args).compile()
    txt = compiled.as_text()
    mod = HloModule(txt)
    res.n_params = len(mod.entry_params())

    # (a) donation landed ---------------------------------------------------
    if check_donation and donate:
        aliased = mod.aliased_param_numbers()
        for pnum, path, nbytes in donated_leaf_params(args, donate, statics):
            if nbytes < DONATION_MIN_BYTES or pnum in aliased:
                continue
            res.violations.append(Violation(
                kind="contract", rule="donation-not-landed", where=where,
                symbol=path, bytes_wasted=nbytes,
                msg=f"donated operand {path} ({nbytes} bytes) has no "
                    f"input_output_alias entry: XLA copied the buffer "
                    f"instead of reusing it — the pool is paid for twice "
                    f"at every call"))

    # (b) no host boundary in traced code -----------------------------------
    for comp, op, target in mod.host_ops():
        detail = f" target={target}" if target else ""
        res.violations.append(Violation(
            kind="contract", rule="host-boundary", where=where,
            symbol=f"{comp}:{op}",
            msg=f"host-crossing op {op}{detail} in computation {comp}: a "
                f"host sync inside traced code serializes every call "
                f"(inside the K-step scan: once per token)"))

    # (d) dtype hygiene -----------------------------------------------------
    if _F64_RE.search(txt):
        res.violations.append(Violation(
            kind="contract", rule="f64", where=where, symbol="module",
            msg="f64 buffers in compiled code (an accidental float64 "
                "promotion — jax_enable_x64 leak or numpy scalar)"))
    if cache_in is not None and rec.get("cache_out") is not None:
        from repro.core.policy import path_str
        out = jax.eval_shape(rec["fn"], *args)
        out_cache = (out[rec["cache_out"]]
                     if isinstance(out, (tuple, list)) else out)
        in_d = {path_str(p): l.dtype for p, l in
                jax.tree_util.tree_flatten_with_path(cache_in)[0]}
        out_d = {path_str(p): l.dtype for p, l in
                 jax.tree_util.tree_flatten_with_path(out_cache)[0]}
        for k, dt_in in in_d.items():
            dt_out = out_d.get(k)
            if dt_out is not None and dt_out != dt_in:
                res.violations.append(Violation(
                    kind="contract", rule="cache-dtype-drift", where=where,
                    symbol=k,
                    msg=f"cache leaf {k} enters {dt_in} but leaves "
                        f"{dt_out}: a silent upcast grows the pool "
                        f"every dispatch"))

    # (e) collective manifest ----------------------------------------------
    if mesh:
        nd = len(jax.devices())
        counts = mod.collectives(nd)["counts"]
        res.collectives = sorted(counts)

    # fingerprint / collectives vs the checked-in manifest ------------------
    if expect is None:
        if not update:
            res.violations.append(Violation(
                kind="contract", rule="fingerprint-missing", where=where,
                symbol="manifest",
                msg="entry has no manifest record: run `python -m "
                    "repro.staticcheck --update` and commit the manifest"))
    else:
        if expect.get("fingerprint") != res.fingerprint:
            res.violations.append(Violation(
                kind="contract", rule="recompile-fingerprint", where=where,
                symbol="signature",
                msg=f"abstract signature drifted "
                    f"({expect.get('fingerprint')} -> {res.fingerprint}): "
                    f"the entry's jit cache key changed — audit for "
                    f"shape/dtype/state-tree drift, then `--update` the "
                    f"manifest deliberately"))
        if mesh and expect.get("collectives") is not None \
                and expect["collectives"] != res.collectives:
            res.violations.append(Violation(
                kind="contract", rule="collective-manifest", where=where,
                symbol="collectives",
                msg=f"collective set changed: expected "
                    f"{expect['collectives']}, compiled "
                    f"{res.collectives}"))
    return res


def check_case(case: Case, manifest: dict, update: bool):
    """All entries of one matrix case.  Returns (violations, manifest
    records, entries checked)."""
    eng = build_engine(case)
    entries = eng.entry_points()
    violations: list[Violation] = []
    records: dict[str, dict] = {}
    for name in case_entry_names(case):
        rec = entries[name]
        args = entry_args(eng, case, name)
        cache_in = (args[rec["cache_arg"]]
                    if rec.get("cache_arg") is not None else None)
        expect = manifest.get(case.name, {}).get(name)
        res = check_entry(case.name, name, rec, args, expect=expect,
                          update=update, mesh=case.mesh,
                          cache_in=cache_in)
        violations.extend(res.violations)
        records[name] = {"fingerprint": res.fingerprint}
        if case.mesh:
            records[name]["collectives"] = res.collectives
    return violations, records, len(records)


# -- kernels triads ---------------------------------------------------------

def kernel_entries() -> dict[str, tuple]:
    """(jitted op, args, static kwargs) for the Pallas kernel wrappers —
    CPU-checkable (the CPU lowering runs Pallas interpret mode)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.fp8_matmul.ops import matmul_fp8_2d
    from repro.kernels.fp8_quant.ops import quantize_fp8
    from repro.kernels.scale_search.ops import sweep

    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (64, 64), jnp.float32)
    alpha = jnp.float32(1.0)
    q, s = jax.eval_shape(
        lambda w_, a_: quantize_fp8(w_, a_, block=32),
        w, alpha)
    x = jnp.zeros((8, 64), jnp.float32)
    alphas = jnp.linspace(0.8, 1.25, 4)
    return {
        "fp8_quant.quantize_fp8": (
            quantize_fp8, (w, alpha), {"block": 32}),
        "fp8_matmul.matmul_fp8_2d": (
            matmul_fp8_2d,
            (x, jax.ShapeDtypeStruct(q.shape, q.dtype),
             jax.ShapeDtypeStruct(s.shape, s.dtype)),
            {"block": 32}),
        "scale_search.sweep": (
            sweep, (w, w, alphas),
            {"block_size": 32, "use_kernel": True}),
    }


def check_kernels(manifest: dict, update: bool):
    """Host-boundary / f64 / fingerprint contracts over the kernel triads
    (donation does not apply: the ops consume live weights)."""
    violations: list[Violation] = []
    records: dict[str, dict] = {}
    for name, (fn, args, kwargs) in kernel_entries().items():
        pairs = tuple(sorted(kwargs.items()))
        # the static kwargs ride as trailing positional (key, value) pairs
        # marked static, so the fingerprint records them by repr instead of
        # abstractifying them (bools/ints would read as weak-typed leaves)
        rec = {"fn": _KwargsLower(fn, kwargs), "donate": (),
               "static_argnums": tuple(range(len(args),
                                             len(args) + len(pairs))),
               "cache_out": None}
        expect = manifest.get("kernels", {}).get(name)
        res = check_entry("kernels", name, rec, args + pairs,
                          expect=expect, update=update,
                          check_donation=False)
        violations.extend(res.violations)
        records[name] = {"fingerprint": res.fingerprint}
    return violations, records, len(records)


class _KwargsLower:
    """Adapter: check_entry lowers positionally; kernel ops take their
    static switches as keywords.  The trailing (key, value) pairs in the
    args tuple (marked static for the fingerprint) are stripped back to
    kwargs here."""

    def __init__(self, fn, kwargs):
        self._fn = fn
        self._kwargs = kwargs

    def lower(self, *args):
        n = len(self._kwargs)
        real = args[:-n] if n else args
        return self._fn.lower(*real, **self._kwargs)


def run_contracts(matrix: str, manifest: dict, update: bool):
    """Run the whole pass.  Returns (violations, new manifest, counters,
    skipped case names)."""
    import jax

    cases = MATRICES[matrix]
    violations: list[Violation] = []
    new_manifest: dict = {}
    skipped: list[str] = []
    n_entries = 0
    for case in cases:
        if case.mesh and len(jax.devices()) < 2:
            skipped.append(
                f"{case.name}: needs >= 2 devices (run via `python -m "
                f"repro.staticcheck`, which forces a 2-device host "
                f"platform)")
            # keep the manifest records so --update on a 1-device host
            # does not erase the mesh expectations
            if case.name in manifest:
                new_manifest[case.name] = manifest[case.name]
            continue
        vs, records, n = check_case(case, manifest, update)
        violations.extend(vs)
        new_manifest[case.name] = records
        n_entries += n
    kvs, krecords, kn = check_kernels(manifest, update)
    violations.extend(kvs)
    new_manifest["kernels"] = krecords
    n_entries += kn
    counters = {"cases": len(cases) - len(skipped), "entries": n_entries}
    return violations, new_manifest, counters, skipped
