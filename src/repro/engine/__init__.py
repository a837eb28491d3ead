"""Device-resident serving engine: continuous batching over a paged KV
pool, prompts prefilled in chunks inside the decode dispatch, real
sampling, opt-in sharded serving.

    from repro.engine import Engine, EngineConfig, SamplingParams

    eng = Engine(model, params, slots=8, cache_len=512, k_steps=8,
                 sampling=SamplingParams(greedy=False, temperature=0.8,
                                         top_k=40))
    outputs = eng.serve(requests, gen_tokens=64)

See engine.py (the serve loop: admission, dispatch, host bookkeeping),
scheduler.py (slot state + K-step dispatch, in-scan chunked prefill),
paged.py (block pool, block tables, device free-list, refcounted sharing +
copy-on-write), prefix.py (host chained-hash prompt-block index —
``Engine(..., prefix_cache=True)``), spec.py (self-speculative decoding:
quantized-draft rounds verified by the full-precision model —
``Engine(..., n_spec=4, draft_params=qtree)``), sampler.py (greedy /
temperature / top-k / top-p), legacy.py (the contiguous-cache host loop,
the parity reference of the engine's tests).
"""
from repro.engine.engine import Engine, EngineConfig
from repro.engine.legacy import serve_host_loop, single_slot_prefill
from repro.engine.paged import (admit_slot, alloc_span, alloc_step,
                                blocks_for, gather_blocks, init_block_state,
                                release_refs, release_slots, span_targets)
from repro.engine.prefix import PrefixIndex, chain_hashes
from repro.engine.sampler import SamplingParams, probs, sample, warp_logits
from repro.engine.scheduler import (init_slot_state, make_decode_dispatch,
                                    make_decode_step)
from repro.engine.spec import (DepthController, greedy_accept,
                               make_spec_dispatch, rejection_accept)

__all__ = [
    "Engine", "EngineConfig", "SamplingParams", "sample", "probs",
    "warp_logits",
    "init_slot_state", "make_decode_dispatch", "make_decode_step",
    "make_spec_dispatch", "greedy_accept", "rejection_accept",
    "DepthController",
    "serve_host_loop", "single_slot_prefill",
    "admit_slot", "alloc_span", "alloc_step", "blocks_for",
    "gather_blocks", "init_block_state", "release_refs", "release_slots",
    "span_targets", "PrefixIndex", "chain_hashes",
]
