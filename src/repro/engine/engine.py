"""Device-resident serving engine: continuous batching over a paged KV
pool, with prompt prefill streamed in chunks through the decode dispatch.

The host keeps only what it must (the request queue, each slot's budget
and its worst-case block reservation, maintained from the dispatch results
it already fetched — no extra syncs); everything per-token lives on device:

* **one dispatch** — a jitted dispatch runs ``k_steps`` scan steps
  (scheduler.make_decode_dispatch); the host syncs once per dispatch to
  drain the emitted-token grid.  Each step runs one decode step for the
  decoding slots and one ``chunk_size``-token prefill piece for the slots
  still prefilling, so long prompts stream instead of stalling decode.
  The step a prompt's last chunk lands, its first token is sampled from
  the chunk's logits.  Chunk pieces are bit-exact vs one-shot prefill
  (same flash tile math with offset masks, SSD state threaded on the
  ``ssm_chunk`` grid); MoE layers serve dropless on every path
  (``moe.apply_moe_dropless``).  ``chunk_size`` 0 derives the piece in
  one place (:func:`default_chunk_size`).
* **paged KV cache** — a global block pool with per-slot block tables and
  a device-resident free-list (engine/paged.py): memory tracks live
  tokens instead of ``slots * cache_len``.  Admission maps a slot's blocks
  and arms its prompt buffer (no model forward); it reserves each
  request's lifetime worst case against the pool (FIFO; requests wait
  when the head doesn't fit), and blocks recycle inside the K-step scan
  as slots drain.  SSM state has no sequence axis and stays per slot.
* **prefix caching** — ``prefix_cache=True`` shares full prompt blocks
  across requests: a host-side chained-hash index (engine/prefix.py) maps
  matched leading blocks into the new slot's table with ``refcount += 1``
  and only the unmatched tail is prefilled; released blocks stay cached
  (the index holds one reference) until LRU eviction makes room.  A
  partially-matched last block is mapped shared and copy-on-write
  protected: the first decode write pops a private copy.  The reservation
  ledger counts only non-shared blocks, so a warm cache admits more
  concurrency from the same pool.  Sharing is content-sound for causal
  attention stacks without position-keyed ring caches or recurrent state;
  SWA / SSM / hybrid configs run with matching disabled.
* **self-speculative decoding** — ``n_spec > 0`` (pass a quantized
  ``draft_params`` tree) swaps each dispatch step for a speculative round:
  the quantized tree drafts up to ``n_spec`` tokens, one full-precision
  multi-token verify forward accepts a prefix (greedy match, or lossless
  rejection sampling for temperature/top-k/top-p), and rejected positions
  roll back per slot (engine/spec.py).  Chunk pieces, CoW prefix writes
  and speculative rounds are orthogonal phases of one scan step.  The
  depth is dynamic by default (``spec_dynamic``): a host-side AIMD
  controller walks it 1..n_spec from the acceptance telemetry — the depth
  is a traced operand, so moves never recompile.  Greedy outputs stay
  token-exact vs the non-speculative engine for any draft.
* **sampling** — greedy / temperature / top-k / top-p via engine.sampler
  with a per-step threaded PRNG key.
* **sharding** — pass ``mesh=`` to place params with
  ``launch.sharding.params_shardings`` (quantized ``wq/data`` / ``wq/scale``
  leaves inherit the dense weight's layout by tree path) and the pool with
  ``cache_shardings``; every jitted entry then runs GSPMD-partitioned.

Greedy outputs are token-exact vs ``engine.legacy.serve_host_loop``, the
contiguous-cache host loop the parity tests compare against.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import paged as P
from repro.engine.prefix import PrefixIndex
from repro.engine.sampler import SamplingParams
from repro.engine.scheduler import init_slot_state, make_decode_dispatch
from repro.models.lm import Model, has_moe, has_ssm
from repro.telemetry.counters import (COUNTER_KEYS, bump, counter_totals,
                                      init_counters)
from repro.telemetry.trace import clock_us, span

_BKEYS = P.BSTATE_KEYS


def _abstract(x):
    """A device array's shape, dtype and placement; anything else as is."""
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(x.shape, x.dtype, weak_type=x.weak_type,
                                sharding=x.sharding if x.committed else None)


def default_chunk_size(mcfg, block_size: int) -> int:
    """Prefill tokens per scan step when ``chunk_size`` is 0: the SSD chunk
    for stacks with SSM state (chunked prefill is bit-exact only on that
    grid), else four blocks, at most the sliding window (a chunk wider than
    the ring would write two of its rows to one ring row)."""
    if mcfg.family in ("ssm", "hybrid"):
        return mcfg.ssm_chunk
    chunk = 4 * block_size
    return min(chunk, mcfg.sliding_window) if mcfg.sliding_window else chunk


@dataclass(frozen=True)
class EngineConfig:
    slots: int = 2          # size of the continuous-batching pool
    cache_len: int = 256    # per-slot capacity (prompt + generated rows)
    k_steps: int = 8        # scan steps per dispatch (1 host sync each)
    sampling: SamplingParams = field(default_factory=SamplingParams)
    seed: int = 0
    paged: bool = True      # must be True: the paged pool is the only cache
    block_size: int = 16    # tokens per KV block
    num_blocks: int = 0     # pool size; 0 -> slots * ceil(cap / block_size)
    chunk_size: int = 0     # prompt tokens prefilled per scan step;
                            # 0 -> default_chunk_size
    prefix_cache: bool = False  # refcounted prompt-block sharing
    n_spec: int = 0         # >0: self-speculative decoding — draft up to
                            # n_spec tokens per round with the quantized
                            # ``draft_params`` tree, verify with one
                            # full-precision forward (pass draft_params= to
                            # Engine).  Composes with prefix_cache:
                            # speculation, chunked prefill and CoW prefix
                            # writes are orthogonal phases of one scan step
    spec_dynamic: bool = True   # move the speculation depth 1..n_spec at
                                # runtime from acceptance telemetry
                                # (spec.DepthController); depth is a traced
                                # operand, so moves never recompile.
                                # False pins depth = n_spec
    check_invariants: bool = False  # assert allocator conservation after
                                    # every admission/dispatch (tests; slow)


class Engine:
    """Continuous-batching serving engine over a built :class:`Model`."""

    def __init__(self, model: Model, params, cfg: EngineConfig | None = None,
                 *, mesh=None, draft_params=None, metrics=None, tracer=None,
                 **kw):
        if cfg is None:
            cfg = EngineConfig(**kw)
        elif kw:
            raise TypeError("pass either cfg= or keyword fields, not both")
        # host-side observability (repro.telemetry) — both optional, both
        # fed exclusively from values the serve loop already fetched, so
        # enabling them changes no jitted signature and adds no host sync
        self.metrics = metrics      # MetricsRegistry | None
        self.tracer = tracer        # Tracer | None
        mcfg = model.cfg
        if mcfg.family in ("vlm", "encdec"):
            raise NotImplementedError(
                "Engine drives LM-style models; vlm/encdec need modality "
                "inputs (see examples/)")
        if not cfg.paged:
            raise ValueError(
                "paged=False: the contiguous cache was removed; the engine "
                "serves only through the paged block pool")
        if not cfg.chunk_size:
            cfg = replace(cfg, chunk_size=default_chunk_size(
                mcfg, cfg.block_size))
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self._moe = has_moe(mcfg)   # expert-layer device counters
        self._ssm = has_ssm(mcfg)   # Mamba-2 decode-kernel counter
        self.params = self._place_params(params) if mesh is not None else params

        sp, K = cfg.sampling, cfg.k_steps
        if K < 1:
            raise ValueError(f"k_steps must be >= 1, got {K}")
        if cfg.n_spec:
            if cfg.n_spec >= K:
                raise ValueError(
                    f"n_spec must be < k_steps (got n_spec={cfg.n_spec}, "
                    f"k_steps={K}): the dispatch runs k_steps speculative "
                    f"rounds and sizes its token grid k_steps*(n_spec+1) — "
                    f"raise k_steps or lower n_spec")
            if draft_params is None:
                raise ValueError(
                    "speculative decoding needs draft_params: a quantized "
                    "copy of the serving weights, e.g. repro.quantize("
                    "params, base, qcfg, mode='storage')[0]")
            if mcfg.sliding_window and cfg.n_spec + 1 > mcfg.sliding_window:
                raise ValueError(
                    f"n_spec + 1 ({cfg.n_spec + 1}) must fit inside the "
                    f"sliding window ({mcfg.sliding_window}): a round's "
                    f"verify span may not wrap the whole ring")
        elif draft_params is not None:
            raise ValueError("draft_params without n_spec > 0 does nothing: "
                             "set n_spec to enable speculative decoding")
        window = mcfg.sliding_window
        cap = min(cfg.cache_len, window) if window else cfg.cache_len
        if window and cap != window:
            raise ValueError(
                f"paged SWA serving needs cache_len >= sliding_window "
                f"({cfg.cache_len} < {window})")
        if window and window % cfg.block_size:
            raise ValueError(
                f"block_size {cfg.block_size} must divide the sliding "
                f"window {window}: ring positions are block-mapped "
                f"(pos % window straddles the block grid otherwise)")
        if window and cfg.chunk_size > window:
            raise ValueError(
                f"chunk_size {cfg.chunk_size} exceeds the sliding window "
                f"{window}: two rows of one chunk would write one ring row")
        if (mcfg.family in ("ssm", "hybrid")
                and cfg.chunk_size % mcfg.ssm_chunk):
            raise ValueError(
                f"chunked prefill over SSM state is bit-exact only on "
                f"the SSD chunk grid: chunk_size {cfg.chunk_size} must "
                f"be a multiple of ssm_chunk {mcfg.ssm_chunk}")
        self._mb = P.blocks_for(cap, cfg.block_size)  # blocks per slot
        self._num_blocks = cfg.num_blocks or cfg.slots * self._mb
        # prompt-block sharing is content-sound only when a block's KV
        # is a pure function of the token prefix: ring caches are
        # position-keyed (mod window) and SSM state is recurrent, so
        # those families run chunked but unshared
        self._can_match = (cfg.prefix_cache
                           and mcfg.family in ("dense", "moe")
                           and not window)
        self._index = PrefixIndex(cfg.block_size)
        self._hold_blocks: set[int] = set()   # index + pending holds
        self._pcache = None                   # persistent cache/state
        self._pstate = None
        self._ctr_live0 = 0     # blocks out of the pool at serve() start
        # Every jitted entry point goes through _register so the compile
        # contracts (repro.staticcheck) and the serve telemetry see one
        # authoritative registry: name -> jitted fn, donated argnums, and
        # where the cache tree sits in the signature / result.
        self._entries: dict[str, dict] = {}
        self._first_args: dict[str, tuple] = {}
        cow, C = cfg.prefix_cache, cfg.chunk_size
        if cfg.n_spec:
            self._draft_params = (self._place_params(draft_params)
                                  if mesh is not None else draft_params)
            self._dispatch_spec = self._register(
                "_dispatch_spec",
                make_decode_dispatch(model, sp, K, cow=cow,
                                     n_spec=cfg.n_spec),
                donate=(2, 3), cache_arg=3, cache_out=1)
            self._dispatch_spec_chunk = self._register(
                "_dispatch_spec_chunk",
                make_decode_dispatch(model, sp, K, cow=cow, chunk=C,
                                     n_spec=cfg.n_spec),
                donate=(2, 3), cache_arg=3, cache_out=1)
        else:
            self._dispatch = self._register(
                "_dispatch", make_decode_dispatch(model, sp, K, cow=cow),
                donate=(1, 2), cache_arg=2, cache_out=1)
            self._dispatch_chunk = self._register(
                "_dispatch_chunk",
                make_decode_dispatch(model, sp, K, cow=cow, chunk=C),
                donate=(1, 2), cache_arg=2, cache_out=1)
        self._admit_chunk = self._register(
            "_admit_chunk", self._admit_chunk_impl, donate=(0, 1),
            cache_arg=0, cache_out=0)
        self._evict = self._register(
            "_evict", self._evict_impl, donate=(0, 1),
            cache_arg=0, cache_out=0)

    # -- jitted entry-point registry ----------------------------------------

    def _register(self, name: str, fun, *, donate: tuple = (),
                  cache_arg: int | None = None,
                  cache_out: int | None = None):
        """Jit ``fun`` and record it as a named engine entry point.

        ``donate`` are the argnums handed to ``donate_argnums`` (the
        compile contracts assert each donated cache/pool buffer actually
        aliases an output); ``cache_arg``/``cache_out`` locate the cache
        tree in the signature and the result tuple so dtype-hygiene checks
        can compare leaf dtypes input -> output."""
        jitted = jax.jit(fun, donate_argnums=donate)
        self._entries[name] = {
            "fn": jitted, "donate": tuple(donate),
            "cache_arg": cache_arg, "cache_out": cache_out,
        }

        def call(*args):
            # the signature of the first call, taken before donation frees
            # the buffers: hlo_texts() lowers the same executable from it
            if name not in self._first_args:
                self._first_args[name] = jax.tree.map(_abstract, args)
            return jitted(*args)
        return call

    def entry_points(self) -> dict[str, dict]:
        """The live jitted entry points of this engine configuration (a
        shallow copy: name -> registry record).  repro.staticcheck lowers
        every record across the config matrix and checks its compile
        contracts; the serve CLI reads compile counts off the same set."""
        return dict(self._entries)

    def compile_counts(self) -> dict[str, int]:
        """Traced-signature count per entry point (jit cache size).  A
        steady-state serve loop holds this at 1 per entry; growth across
        dispatches means an avoidable recompile (shape drift or weak-type
        literals in the argument tree)."""
        return {name: int(e["fn"]._cache_size())
                for name, e in self._entries.items()}

    def hlo_texts(self) -> dict[str, str]:
        """``{module name: compiled HLO text}`` of each dispatch entry that
        has run, lowered from the shapes, dtypes and shardings of its first
        call: the executable the serve loop ran (with a persistent compile
        cache, a cache hit).  ``repro.telemetry.scopes.op_scopes`` reads the
        named scopes of its device ops from it."""
        out = {}
        for name, args in self._first_args.items():
            if name.startswith("_dispatch"):
                text = self._entries[name]["fn"].lower(*args).compile() \
                    .as_text()
                out[text.split(",", 1)[0].split()[-1]] = text
        return out

    # -- sharded placement --------------------------------------------------

    def _place_params(self, params):
        from repro.launch.sharding import params_shardings
        shard = params_shardings(jax.eval_shape(lambda: params),
                                 self.model.cfg, self.mesh)
        return jax.device_put(params, shard)

    def _place_cache(self, cache):
        from repro.launch.sharding import cache_shardings
        shard = cache_shardings(jax.eval_shape(lambda: cache),
                                self.model.cfg, self.mesh)
        return jax.device_put(cache, shard)

    # -- admission ----------------------------------------------------------

    def _admit_chunk_impl(self, cache, state, slot, tokens, L, shared_ids,
                          n_shared, n_new, n_retained, pf_start,
                          shared_until, budget):
        """Admit one request into ``slot`` for in-scan chunked prefill: no
        model forward here — release the stale slot, map shared (prefix-hit)
        blocks + pop fresh ones, zero the slot's recurrent state, and arm
        the prompt buffer.  The first token is sampled inside the dispatch
        when the last chunk lands."""
        B = state["active"].shape[0]
        bstate = {k: cache[k] for k in _BKEYS}
        nf0 = bstate["n_free"]
        done = jnp.zeros((B,), bool).at[slot].set(True)
        bstate = P.release_slots(bstate, done)
        nf1 = bstate["n_free"]
        bstate, new_ids = P.admit_slot(bstate, slot, shared_ids, n_shared,
                                       n_new, n_retained, self._mb)

        def zero_group(group):
            return {lk: {name: (leaf if name in ("pk", "pv")
                                else leaf.at[:, slot].set(0))
                         for name, leaf in lv.items()}
                    for lk, lv in group.items()}

        new = dict(cache)
        new.update(bstate)
        new["stack"] = zero_group(cache["stack"])
        if "prefix" in cache:
            new["prefix"] = zero_group(cache["prefix"])
        new["lengths"] = cache["lengths"].at[slot].set(pf_start)
        state = {
            **state,
            "active": state["active"].at[slot].set(False),
            "remaining": state["remaining"].at[slot].set(0),
            "prompt": state["prompt"].at[slot].set(tokens),
            "pf_pos": state["pf_pos"].at[slot].set(pf_start),
            "pf_len": state["pf_len"].at[slot].set(L),
            "budget": state["budget"].at[slot].set(budget),
            "pf_shared": state["pf_shared"].at[slot].set(shared_until),
            # pf_start (not shared_until): tokens actually skipped, the
            # same quantity the host's stats["prefix_hits"] accumulates
            "ctr": bump(state["ctr"],
                        prefix_hit_tokens=pf_start,
                        blocks_released=nf1 - nf0,
                        blocks_popped=nf1 - bstate["n_free"]),
        }
        return new, state, new_ids

    @staticmethod
    def _evict_impl(cache, state, ids):
        """Drop host holds on ``ids`` and count the blocks that actually
        hit the free stack on the device counter tree."""
        nf0 = cache["n_free"]
        bstate = P.release_refs({k: cache[k] for k in _BKEYS}, ids)
        state = {**state,
                 "ctr": bump(state["ctr"],
                             blocks_released=bstate["n_free"] - nf0)}
        return {**cache, **bstate}, state

    # -- allocator invariants (check_invariants=True) -----------------------

    def _assert_invariants(self, cache, state=None) -> None:
        """Conservation of the block pool, checked on the device truth:
        free stack and referenced blocks partition the pool, and every
        block's refcount equals its live table references plus the host's
        index/pending hold.  With ``state`` the device counter tree is
        checked too: pops minus releases must account for every block out
        of the free stack since the counters were zeroed
        ("popped == released + live"), and every drafted position must be
        either accepted or rejected."""
        bs = jax.device_get({k: cache[k] for k in _BKEYS})
        NB = self._num_blocks
        n_free = int(bs["n_free"])
        free = [int(b) for b in bs["free"][:n_free]]
        assert len(set(free)) == n_free, "free stack holds duplicates"
        ref = np.asarray(bs["ref"])
        held = {b for b in range(NB) if ref[b] > 0}
        assert not (set(free) & held), "block both free and referenced"
        assert n_free + len(held) == NB, (
            f"pool leak: {n_free} free + {len(held)} held != {NB}")
        tbl = np.asarray(bs["tbl"])
        counts = np.zeros(NB, np.int64)
        for b in tbl[tbl >= 0].reshape(-1):
            counts[b] += 1
        holds = self._hold_blocks
        for b in range(NB):
            expect = counts[b] + (1 if b in holds else 0)
            assert ref[b] == expect, (
                f"block {b}: ref {ref[b]} != tables {counts[b]} + "
                f"hold {int(b in holds)}")
        if state is not None:
            ctr = counter_totals(jax.device_get(state["ctr"]))
            live0 = self._ctr_live0
            popped, released = ctr["blocks_popped"], ctr["blocks_released"]
            assert live0 + popped - released == NB - n_free, (
                f"counter leak: base {live0} + popped {popped} - released "
                f"{released} != live {NB - n_free}")
            assert ctr["drafted"] == ctr["accepted"] + ctr["rejected"], (
                f"spec counter leak: drafted {ctr['drafted']} != accepted "
                f"{ctr['accepted']} + rejected {ctr['rejected']}")

    # -- serve --------------------------------------------------------------

    def _spec_controller(self):
        """Fresh dynamic-depth policy for one serve() call (spec.py).  With
        ``spec_dynamic=False`` the thresholds are pushed out of [0, 1], so
        no acceptance rate ever moves the depth off ``n_spec`` — one code
        path either way."""
        from repro.engine.spec import DepthController
        if self.cfg.spec_dynamic:
            return DepthController(self.cfg.n_spec)
        return DepthController(self.cfg.n_spec, lo=-1.0, hi=2.0)

    def _blocks_needed(self, prompt_len: int, gen_tokens: int) -> int:
        """Worst-case pool blocks one request can ever hold: SWA rings page
        the whole window; dense requests write ``prompt + gen - 1`` cache
        rows over their lifetime (capacity-clamped: writes past the slot's
        capacity route to the trash block).  Speculative rounds overshoot by up
        to ``n_spec`` rows past the budget before rolling back (the last
        round's span), so the reservation covers that transient too."""
        if self.model.cfg.sliding_window:
            return self._mb
        return min(P.blocks_for(prompt_len + gen_tokens - 1 + self.cfg.n_spec,
                                self.cfg.block_size), self._mb)

    # -- telemetry (repro.telemetry) ----------------------------------------

    def _request_done(self, prompt_len, gen_len, t_enq, t_admit, t_first,
                      prefix_hit_frac=None):
        """Record one finished request's lifecycle histograms (no-op
        without a registry; all inputs are host floats already in hand)."""
        m = self.metrics
        if m is None:
            return
        now = time.perf_counter()
        m.counter("requests.completed").inc()
        m.histogram("request.ttft_s", unit="s").observe(t_first - t_enq)
        m.histogram("request.queue_wait_s", unit="s").observe(
            t_admit - t_enq)
        m.histogram("request.tpot_s", unit="s").observe(
            (now - t_first) / max(gen_len - 1, 1))
        m.histogram("request.prompt_len", lo=1.0, hi=1e6,
                    unit="tokens").observe(prompt_len)
        m.histogram("request.gen_len", lo=1.0, hi=1e6,
                    unit="tokens").observe(gen_len)
        if prefix_hit_frac is not None:
            m.histogram("request.prefix_hit_frac", lo=1e-3,
                        hi=1.0).observe(prefix_hit_frac)

    def _count_dispatch(self, stats, totals, prev, spec_ctl):
        """Fold one synced dispatch into ``stats``: its speculative deltas
        (``totals - prev`` of the device counters) move the depth policy;
        with a tracer, counter-track samples of the counter tree."""
        K = self.cfg.k_steps
        tr = self.tracer
        if self.cfg.n_spec:
            d_dr = totals["drafted"] - prev["drafted"]
            d_ac = totals["accepted"] - prev["accepted"]
            stats["draft_tokens"] += d_dr
            stats["draft_accepted"] += d_ac
            stats["spec_rounds"] += K
            if tr:
                tr.counter("spec", {"depth": spec_ctl.depth,
                                    "drafted": d_dr, "accepted": d_ac})
            stats["spec_depth"] = spec_ctl.update(d_dr, d_ac)
        stats["host_syncs"] += 1
        stats["dispatches"] += 1
        stats["decode_steps"] += K
        if tr is None:
            return
        tr.counter("tokens", {"emitted": totals["tokens"]})
        live = (self._ctr_live0
                + totals["blocks_popped"] - totals["blocks_released"])
        tr.counter("blocks", {"live": live, "cow": totals["cow_copies"]})

    def _finalize_serve(self, stats, ctr_host):
        """End-of-serve telemetry: expose the device counters through
        ``stats["counters"]`` and fold them plus the allocator / spec
        gauges into the registry.  ``ctr_host`` is the last counter tree
        the dispatch sync fetched (every request's first token comes from
        a dispatch, so one ran)."""
        totals = counter_totals(ctr_host)
        stats["counters"] = totals
        m = self.metrics
        if m is None:
            return
        for k, v in totals.items():
            m.counter(f"device.{k}").inc(v)
        if self.cfg.n_spec:
            m.gauge("spec.depth").set(stats["spec_depth"])
            acc = m.gauge("spec.acceptance_rate")   # None -> "n/a"
            if totals["drafted"]:
                acc.set(totals["accepted"] / totals["drafted"])
        live = (self._ctr_live0
                + totals["blocks_popped"] - totals["blocks_released"])
        m.gauge("alloc.live_blocks").set(live)
        m.gauge("alloc.free_blocks").set(self._num_blocks - live)
        holds = len(self._hold_blocks)
        m.gauge("alloc.index_holds").set(holds)
        m.gauge("alloc.ledger_headroom").set(self._num_blocks - holds)
        m.counter("prefix.evictions").inc(stats["prefix_evictions"])

    def serve(self, requests, *, gen_tokens: int, seed: int | None = None,
              return_stats: bool = False):
        """Serve ``requests`` (1-D token arrays); each gets ``gen_tokens``
        generated tokens.  Returns outputs in request order (and a stats
        dict when ``return_stats``)."""
        cfg, model = self.cfg, self.model
        B, K, C = cfg.slots, cfg.k_steps, cfg.chunk_size
        requests = [jnp.asarray(r, jnp.int32).reshape(-1) for r in requests]
        stats = {"host_syncs": 0, "dispatches": 0, "prefill_calls": 0,
                 "decode_steps": 0, "tokens": 0, "prefill_tokens": 0,
                 "counters": dict.fromkeys(COUNTER_KEYS, 0)}
        spec_ctl = self._spec_controller() if cfg.n_spec else None
        if cfg.n_spec:
            stats.update(spec_rounds=0, draft_tokens=0, draft_accepted=0,
                         spec_depth=spec_ctl.depth)
        if gen_tokens < 1 or not requests:
            return ([], stats) if return_stats else []
        bs = cfg.block_size
        pcap = cfg.cache_len
        persist = cfg.prefix_cache
        for r in requests:
            L = int(r.shape[0])
            if L > pcap:
                raise ValueError(
                    f"prompt of {L} tokens exceeds cache_len {pcap}: "
                    f"prompts stream through the slot's paged cache")
            need = self._blocks_needed(L, gen_tokens)
            if need > self._num_blocks:
                raise ValueError(
                    f"request of {L} tokens needs {need} blocks but the "
                    f"pool has {self._num_blocks}")

        if persist and self._pcache is not None:
            cache, state = self._pcache, self._pstate
            self._pcache = self._pstate = None  # buffers are donated below
        else:
            cache = model.init_paged_cache(B, cfg.cache_len,
                                           block_size=bs,
                                           num_blocks=self._num_blocks)
            state = init_slot_state(B, prompt_cap=pcap, moe=self._moe,
                                    ssm=self._ssm)
            if self.mesh is not None:
                cache = self._place_cache(cache)
            # eviction first runs once the index has filled the pool, a
            # serve or more later: compile it now, with the cache, so no
            # later serve stalls on a compile (every id -1: no release)
            cache, state = self._evict(
                cache, state, jnp.full((self._num_blocks,), -1, jnp.int32))
        stats["cache_bytes"] = sum(
            l.size * l.dtype.itemsize for l in jax.tree.leaves(cache))
        stats["prefix_hits"] = 0
        stats["prefix_evictions"] = 0
        # zero the device counters for this serve() (host-side tree
        # rebuild — covers a reused persistent state).  Index-held blocks
        # survive across serves, so conservation baselines on them.
        state = {**state, "ctr": init_counters(self._moe, self._ssm)}
        self._ctr_live0 = len(self._hold_blocks)
        tr = self.tracer
        t_enq = time.perf_counter()
        ctr_host = None
        ctr_stale = False   # a release ran on the device since ctr_host
        ctr_prev = dict.fromkeys(COUNTER_KEYS, 0)
        req_admit: dict[int, float] = {}
        req_first: dict[int, float] = {}
        req_pf: dict[int, float] = {}    # prefix-hit fraction per request
        slot_t0us = [0.0] * B            # admission trace clock per slot

        key = jax.random.PRNGKey(cfg.seed if seed is None else seed)
        queue = deque(range(len(requests)))
        outputs: dict[int, list[int]] = {}
        slot_rid = [-1] * B
        slot_rem = [0] * B
        slot_rsv = [0] * B       # slot-private worst-case blocks
        slot_pf = [0] * B        # prompt tokens left to prefill (mirror)
        slot_keys = [[] for _ in range(B)]   # pinned index keys per slot
        slot_pend = [None] * B   # (tokens, first_block, ids) to register

        def drop_holds(ids):
            """Release host holds on ``ids`` (eviction / duplicate unwind);
            padded to the pool size so the jitted release compiles once."""
            nonlocal cache, state, ctr_stale
            arr = np.full((self._num_blocks,), -1, np.int32)
            arr[:len(ids)] = ids
            cache, state = self._evict(cache, state, jnp.asarray(arr))
            self._hold_blocks.difference_update(ids)
            ctr_stale = True

        def try_evict(want: int) -> int:
            ids = self._index.evict(want) if self._can_match else []
            if ids:
                with span("engine", "evict", tr, blocks=len(ids)):
                    drop_holds(ids)
                stats["prefix_evictions"] += len(ids)
            return len(ids)

        while queue or any(r >= 0 for r in slot_rid):
            free = [s for s in range(B) if slot_rid[s] < 0]
            while queue and free:
                rid = queue[0]
                prompt = requests[rid]
                L = int(prompt.shape[0])
                toks_np = np.asarray(prompt)
                full, part_len = L // bs, L % bs
                # A request's own prefix hits are pinned while it runs, so
                # they can crowd a tight pool out of reach (e.g. a warm
                # partial hit needing its CoW spare with every block cached
                # and self-pinned).  With running slots we FIFO-wait; with
                # an IDLE pool there is nothing to wait for, so each retry
                # unpins and force-evicts (own matches included) and
                # re-matches against the shrunken index — admission decays
                # toward a cold prefill, which the pool-size validation
                # guarantees fits.
                fits = False
                for _ in range(len(self._index) + 2):
                    matched_ids: list[int] = []
                    partial_id = None
                    keys: list = []
                    if self._can_match:  # excludes SWA/SSM/hybrid
                        matched_ids, partial_id, keys = self._index.match(
                            toks_np)
                        self._index.pin(keys)
                    matched_full = len(matched_ids)
                    partial_hit = partial_id is not None
                    matched_tokens = L if partial_hit else matched_full * bs
                    pf_start = min(matched_tokens, L - 1)
                    if model.cfg.sliding_window:
                        n_shared, n_new, n_ret = 0, self._mb, 0
                        shared = []
                        slot_need, hold_need = self._mb, 0
                    else:
                        new_full = full - matched_full
                        tail_new = 1 if (part_len and not partial_hit) \
                            else 0
                        n_new = new_full + tail_new
                        n_shared = matched_full + (1 if partial_hit else 0)
                        shared = matched_ids + ([partial_id] if partial_hit
                                                else [])
                        n_ret = new_full if self._can_match else 0
                        decode_alloc = (self._blocks_needed(L, gen_tokens)
                                        - P.blocks_for(L, bs))
                        cow_extra = 1 if (partial_hit and gen_tokens > 1) \
                            else 0
                        slot_need = (n_new - n_ret) + decode_alloc \
                            + cow_extra
                        hold_need = n_ret
                    demand = (sum(slot_rsv) + len(self._hold_blocks)
                              + slot_need + hold_need - self._num_blocks)
                    if demand <= 0 or try_evict(demand) >= demand:
                        fits = True
                        break
                    self._index.unpin(keys)
                    if any(r >= 0 for r in slot_rid):
                        break   # FIFO: running slots will drain/unpin
                    if try_evict(demand) == 0:
                        break   # nothing cached left to reclaim
                if not fits:
                    break
                s = free.pop(0)
                queue.popleft()
                with span("engine", "admit", tr, prompt_len=L,
                          prefix_hit=pf_start):
                    shared_arr = np.full((self._mb,), -1, np.int32)
                    shared_arr[:len(shared)] = shared
                    cache, state, new_ids = self._admit_chunk(
                        cache, state, jnp.int32(s),
                        jnp.asarray(np.pad(toks_np, (0, pcap - L)),
                                    jnp.int32),
                        jnp.int32(L), jnp.asarray(shared_arr),
                        jnp.int32(n_shared), jnp.int32(n_new),
                        jnp.int32(n_ret), jnp.int32(pf_start),
                        jnp.int32(matched_tokens), jnp.int32(gen_tokens - 1))
                    slot_rid[s], slot_rem[s] = rid, gen_tokens
                    slot_rsv[s] = slot_need
                    slot_pf[s] = L - pf_start
                    slot_keys[s] = keys
                    outputs[rid] = []
                    req_admit[rid] = time.perf_counter()
                    req_pf[rid] = pf_start / L if L else 0.0
                    stats["prefill_tokens"] += L - pf_start
                    stats["prefix_hits"] += pf_start  # tokens NOT recomputed
                    stats["prefill_calls"] += 1
                    if n_ret:
                        ids = [int(i) for i in
                               jax.device_get(new_ids)[:n_ret]]
                        stats["host_syncs"] += 1
                        self._hold_blocks.update(ids)
                        slot_pend[s] = (toks_np, matched_full, ids)
                    if cfg.check_invariants:
                        self._assert_invariants(cache, state)
                slot_t0us[s] = clock_us()
            if not any(r >= 0 for r in slot_rid):
                assert not queue, "admission stalled with an idle pool"
                continue

            prefilling = any(p > 0 for p in slot_pf)
            kind = ("spec_" if cfg.n_spec else "") + \
                ("chunk" if prefilling else "decode")
            with span("engine", "dispatch", tr, dispatch=kind):
                key, sub = jax.random.split(key)
                if cfg.n_spec:
                    dispatch = (self._dispatch_spec_chunk if prefilling
                                else self._dispatch_spec)
                    state, cache, toks, emitted = dispatch(
                        self.params, self._draft_params, state, cache,
                        jnp.int32(spec_ctl.depth), sub)
                else:
                    dispatch = (self._dispatch_chunk if prefilling
                                else self._dispatch)
                    state, cache, toks, emitted = dispatch(
                        self.params, state, cache, sub)
            # the counter tree rides the returned state: same sync, no cost
            with span("engine", "sync", tr):
                toks_h, em_h, ctr_host = jax.device_get(
                    (toks, emitted, state["ctr"]))
                ctr_stale = False
            with span("engine", "host", tr):
                totals = counter_totals(ctr_host)
                self._count_dispatch(stats, totals, ctr_prev, spec_ctl)
                ctr_prev = totals
                for s in range(B):
                    if slot_rid[s] < 0 or slot_pf[s] <= 0:
                        continue
                    slot_pf[s] = max(0, slot_pf[s] - K * C)
                    if slot_pf[s] > 0:
                        continue
                    if tr:
                        tr.complete("prefill-chunk", f"req{slot_rid[s]}",
                                    slot_t0us[s])
                    if slot_pend[s] is not None:
                        # the slot's new full prompt blocks now hold real KV:
                        # publish them to the prefix index (duplicates lose
                        # their pre-retained hold and die with the slot)
                        toks_np, first_block, ids = slot_pend[s]
                        slot_pend[s] = None
                        dups = self._index.register(toks_np, ids, first_block)
                        if dups:
                            drop_holds(dups)
                            slot_rsv[s] += len(dups)
                        dup_set = set(dups)
                        nkeys = self._index.keys_for(toks_np,
                                                     first_block + len(ids))
                        reg_keys = [k for k, bid in
                                    zip(nkeys[first_block:], ids)
                                    if bid not in dup_set]
                        self._index.pin(reg_keys)
                        slot_keys[s] = slot_keys[s] + reg_keys
                if cfg.check_invariants:
                    self._assert_invariants(cache, state)
                for s in range(B):
                    r = slot_rid[s]
                    if r < 0:
                        continue
                    row = [int(t) for t in toks_h[s][em_h[s]]]
                    if row and r not in req_first:
                        req_first[r] = time.perf_counter()
                    outputs[r].extend(row)
                    stats["tokens"] += len(row)
                    slot_rem[s] -= len(row)
                    if slot_rem[s] <= 0:
                        assert slot_pend[s] is None, \
                            "slot finished before its prompt was prefilled"
                        slot_rid[s] = -1
                        slot_rsv[s] = 0
                        self._index.unpin(slot_keys[s])
                        slot_keys[s] = []
                        self._request_done(
                            int(requests[r].shape[0]), gen_tokens, t_enq,
                            req_admit[r], req_first.get(r, req_admit[r]),
                            prefix_hit_frac=req_pf.get(r))

        if ctr_stale:   # holds dropped in the last host work freed blocks
            ctr_host = jax.device_get(state["ctr"])
            stats["host_syncs"] += 1
        self._finalize_serve(stats, ctr_host)
        if persist:
            self._pcache, self._pstate = cache, state
        outs = [outputs[i] for i in sorted(outputs)]
        return (outs, stats) if return_stats else outs
