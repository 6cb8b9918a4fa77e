"""Serving subsystem: continuous-batching inference over a paged KV cache.

The first inference-side subsystem of the rebuild (ROADMAP item 4 —
"millions of users" needs a serving path, not just training throughput),
grown in round 14 into the production scale-out shape (ROADMAP item 2):
copy-on-write prefix sharing, disaggregated prefill/decode, and
tensor-parallel paged decode.  Pieces, each its own module:

* :mod:`.page_allocator` — refcounted host-side block allocator (page
  ids, per-sequence block tables, prefix-hash trie for copy-on-write
  prompt sharing, typed OOM);
* :mod:`.kv_cache` — the preallocated ``[L, P, S, *entry]`` device pools
  (bf16 pages by default) + in-graph scatter writers, the fork-on-write
  page copy, and the disaggregation transfer receiver;
* :mod:`ops.paged_attention <chainermn_tpu.ops.paged_attention>` — the
  decode hot loop's gather-through-the-block-table attention step
  (``CHAINERMN_TPU_PAGED_ATTN=dense`` escape hatch), the suffix-prefill
  attention for prefix hits, and the tensor-parallel head sharding;
* :mod:`.scheduler` — open-loop admission, per-tenant round-robin
  fairness, refcount-aware preemption-by-eviction (typed
  ``EvictionStalledError`` livelock guard), typed backpressure;
* :mod:`.engine` — the prefill/decode split wired together as bucketed
  jit programs over the shared pools, with the prefix cache, the
  disaggregated slices (``CHAINERMN_TPU_SERVE_DISAGG``), the ``tp``
  mesh axis, and — round 20 (ISSUE 20) — speculative decoding
  (``spec_k``: n-gram or draft-model proposals verified K+1 positions
  per dispatch, bit-identical to vanilla greedy;
  ``CHAINERMN_TPU_SERVE_SPEC=off`` hatch) plus chunked prefill
  (``chunk_tokens``: long prompts stream in page-multiple chunks
  between decode steps instead of head-of-line-blocking them);
* :mod:`.fleet` / :mod:`.router` — round 16 (ISSUE 15): the elastic
  serving fleet — decode replicas in a ``role="fleet"`` membership
  group behind a per-tenant fair router, preempted replicas' in-flight
  sequences replayed on survivors with zero drops, cold joiners
  weight-synced over a multicast tree in O(log N) rounds
  (``CHAINERMN_TPU_FLEET=off`` = single-engine hatch).

Measurement: ``python3 -m benchmark.run --workload gpt2m-serve-chat``
(the cells of ``BENCHMARK.json``: completed tokens/sec and the latency
tails under a seeded open-loop load, per-layer metrics from the trace);
structure committed in ``tools/serving_budgets.json`` and gated tier-1
by ``tests/test_serving_budget.py``.
Design notes: ``docs/serving.md``.
"""

from .engine import (ServingEngine, decode_program, ngram_propose,
                     prefill_program, prefix_prefill_program,
                     serve_disagg_mode, serve_spec_k, spec_verify_program)
from .errors import (EvictionStalledError, PagePoolExhaustedError,
                     QueueSaturatedError, ServingError,
                     UnsupportedProgramError)
from .fleet import (FleetWorker, LocalReplica, QueueDepthScalePolicy,
                    RemoteReplica, ReplicaFleet, fleet_mode)
from .kv_cache import (PagedKVCache, PerSequence, copy_page, insert_pages,
                       write_prompt_kv, write_prompt_kv_at, write_span_kv,
                       write_token_kv)
from .page_allocator import BlockAllocator
from .router import FleetRouter, NoLiveReplicaError
from .scheduler import Request, RequestScheduler

__all__ = [
    "ServingEngine", "prefill_program", "prefix_prefill_program",
    "decode_program", "serve_disagg_mode",
    # round 20 (ISSUE 20): speculative decoding + chunked prefill
    "spec_verify_program", "ngram_propose", "serve_spec_k",
    "write_span_kv",
    "PagedKVCache", "PerSequence", "write_prompt_kv", "write_prompt_kv_at",
    "write_token_kv", "copy_page", "insert_pages",
    "BlockAllocator", "Request", "RequestScheduler",
    "ServingError", "PagePoolExhaustedError", "QueueSaturatedError",
    "EvictionStalledError", "UnsupportedProgramError",
    # round 16 (ISSUE 15): the elastic serving fleet
    "ReplicaFleet", "FleetRouter", "LocalReplica", "RemoteReplica",
    "FleetWorker", "QueueDepthScalePolicy", "fleet_mode",
    "NoLiveReplicaError",
]
