"""Continuous-batching serving engine: prefill/decode split over a paged cache.

The reference's marquee trick — keep the device busy by overlapping the
slow path behind the hot loop — applied to inference.  The engine owns
the scheduling, the pages, the buckets and the spans; the MODEL owns its
block (PR 27): it declares what a token leaves in the cache
(``serve_cache_entry()``: K and V of ``[H · D]`` for a GPT-2-shaped
model, one latent vector for a latent-attention one) and provides the
programs' bodies (``serve_prefill`` / ``serve_suffix_prefill`` /
``serve_decode``) over the page pools.  Two compiled
programs share the pools:

* **prefill** (one request at a time): the prompt runs through the
  model's normal attention forward (``ops.attention`` — the PR 4
  kernels on TPU, backward never traced), each layer's cache entries
  scattering into the request's pages, and the last valid position's
  logits produce the first generated token.  Prompt lengths are PADDED
  to a bucket (powers of two), so ragged prompts reuse a small fixed
  set of compiled programs.
* **decode** (the whole running batch, one token per sequence): a
  single-query step per layer — write the token's entry into its page,
  then attend over the batch's context gathered through the block
  tables (:func:`~chainermn_tpu.ops.paged_attention.paged_decode_attention`
  for K and V, ``paged_latent_attention`` for latents).  The batch
  dimension is padded to a bucket too, so sequences joining and leaving
  the running batch NEVER retrace — the engine counts traces
  (``prefill_traces``/``decode_traces``) and the tests pin it.

Round 14 (ISSUE 13) adds the production scale-out legs:

* **copy-on-write prefix sharing** (``prefix_cache=True``): admission
  matches the prompt against the allocator's prefix-hash trie; matched
  pages are SHARED (refcount++) and only the unmatched suffix prefills
  — through :func:`prefix_prefill_program`, which reads the shared
  prefix via the same one-gather-per-pool shape as decode and runs
  ZERO flash kernels over shared pages.  A match ending mid-page forks
  that page first (in-graph copy, ``copy_page``) so the borrower's
  writes never touch the provider's bytes; the decode trajectory of a
  shared request is bit-identical to its unshared solo run.
* **disaggregated prefill/decode** (``disagg=True`` /
  ``CHAINERMN_TPU_SERVE_DISAGG``): full prefills run on a PREFILL
  device against a scratch pool (prefill is FLOP-bound; decode is
  HBM-bound — the PR 3/PR 4 rooflines want different hardware), and
  finished pages ship slice-to-slice (an ICI copy on real pods) into
  the decode pool, metered by ``transferred_page_bytes``.  Prefix-HIT
  suffix prefills run against the decode pool directly (they must read
  the shared pages, and their FLOPs are exactly what the hit already
  saved).  ``CHAINERMN_TPU_SERVE_DISAGG=off`` is the single-mesh
  escape hatch — trajectory-identical, pinned by test.
* **tensor-parallel decode** (``tp=K``): the KV pools are laid out per
  shard — sharded over the HEAD axis of a ``tp`` mesh (the ulysses
  head-sharding layout) — and both programs compile under GSPMD with
  each shard reading only its own heads' cache bytes
  (``ops.paged_attention.head_sharding`` pins the gathers).  Logits
  match the single-chip decode at fp32 tolerance (parity-gated).

Round 20 (ISSUE 20) adds the raw per-chip speed legs:

* **speculative decoding** (``spec_k=K``): a draft — the built-in
  n-gram self-draft by default, or a small ``draft_model=`` — proposes
  K tokens per sequence per step, and the target scores all ``K + 1``
  positions in ONE dispatch through :func:`spec_verify_program`
  (multi-query paged attention over the same block tables).  Greedy
  accept/reject truncates at the first mismatch, so the output is
  BIT-IDENTICAL to vanilla greedy decode — the draft only ever buys
  speed, never changes a token.  Rollback of rejected speculative KV
  is a position-counter rewind: the writes were ``mode="drop"``-fenced
  scatters into pages the sequence already owns, stale slots are
  masked by ``ctx_len``/causality, and the next step overwrites them.
  Draft KV pages live in the same refcounted ``BlockAllocator`` pool
  (the draft pool is indexed by the SAME block tables).
  ``CHAINERMN_TPU_SERVE_SPEC=off`` is the escape hatch.
* **chunked prefill** (``chunk_tokens=C``): prompts whose unmatched
  remainder exceeds ``C`` admit in page-multiple chunks of ``C``
  tokens, interleaved with decode steps under a per-step token budget
  (``chunk_budget``, default one chunk per step) — a 16k prompt no
  longer occupies whole engine steps while short chat requests queue
  behind it.  Chunks reuse :func:`prefix_prefill_program`'s offset
  writer (``start`` = the chunk cursor; chunk 0 degenerates to
  ``start=0``), prefill buckets top out at ``C`` (prompts above the
  largest bucket now route to chunking instead of the ``_bucket``
  ValueError), and mid-chunk requests are evictable: pages freed,
  chunk cursor reset by the scheduler's requeue (recompute from chunk
  0 on re-admit — the eviction idiom, applied before any token
  exists).  On the disagg split, prefix-miss chunks run on the
  PREFILL slice against the scratch pool (at most one mid-chunk miss
  in flight — single scratch) and the finished pages ship once, after
  the last chunk; prefix-hit chunks run against the decode pool like
  suffix prefills always have.

Host work per step is scheduling metadata only (block tables, positions,
sampled tokens — a few int32s per sequence); KV bytes never leave the
device, and on real accelerators the pools are DONATED through both
programs so XLA updates pages in place (PR 3's donation discipline; on
the CPU test backend donation is skipped — it is a no-op there and only
generates warnings).

**One decode run in flight** (PR 46).  A decode run's input tokens are
the previous run's ``nxt``, which the device already holds, and its
positions and block tables grow by one a run whatever the tokens are.
So :meth:`ServingEngine.step` dispatches decode run ``n + 1`` BEFORE it
fetches run ``n``: the tokens go from one run to the next on the device
(``nxt`` itself where every lane keeps its row, else one small gather,
:func:`next_tokens_program`, that also takes the first tokens of the
lanes admitted since), and the host learns run ``n``'s tokens, records
them and builds the next operands while run ``n + 1`` executes.  The
order falls back to dispatch-then-fetch, by what the engine can see and
no switch, for ``spec_k`` (the accepted length decides the next
positions), while a prompt is mid-chunk, on the disaggregated split, and
it lands the run in flight first when the pool runs dry (a victim's
tokens have to be known before they fold into its prompt).

Scheduling (``serving.scheduler``): open-loop admission at decode-step
granularity with per-tenant round-robin fairness; when the page pool
runs dry the youngest running sequence OWNING at least one unique page
is evicted (pages freed, request re-queued front-of-line with its
generated tokens folded into the prompt — recompute on re-admit) and
the step proceeds; if no victim would free anything the typed
``EvictionStalledError`` fires instead of spinning (the prefix-sharing
livelock guard).
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability
from ..core.link import bind_state, cast_params, extract_state
from ..ops.paged_attention import paged_attn_mode
from ..utils.compat import call_with_frame_room
from .errors import PagePoolExhaustedError, UnsupportedProgramError
from .kv_cache import PagedKVCache, PerSequence, copy_page, insert_pages
from .page_allocator import BlockAllocator
from .scheduler import RequestScheduler

__all__ = ["ServingEngine", "prefill_program", "prefix_prefill_program",
           "decode_program", "spec_verify_program", "next_tokens_program",
           "ngram_propose",
           "serve_disagg_mode", "serve_spec_k"]


def serve_disagg_mode(disagg=None):
    """Resolve the disaggregation knob: ``CHAINERMN_TPU_SERVE_DISAGG=off``
    is the single-mesh escape hatch and wins over everything (the
    disagg-on trajectory is pinned identical to it, so the hatch is
    always safe); ``on``/``1`` enables when the constructor left the
    argument ``None``; default off.  Resolved ONCE at engine
    construction, like the paged-attention mode."""
    env = os.environ.get("CHAINERMN_TPU_SERVE_DISAGG", "").lower()
    if env == "off":
        return False
    if disagg is not None:
        return bool(disagg)
    return env in ("on", "1")


def serve_spec_k(spec_k=0):
    """Resolve the speculative-decoding knob:
    ``CHAINERMN_TPU_SERVE_SPEC=off`` forces vanilla one-token decode
    regardless of the constructor (always safe — the spec-on trajectory
    is pinned bit-identical to it).  Resolved ONCE at engine
    construction, like the paged-attention and disagg modes."""
    if os.environ.get("CHAINERMN_TPU_SERVE_SPEC", "").lower() == "off":
        return 0
    return int(spec_k or 0)


def ngram_propose(history, k, n=3):
    """The built-in self-speculative draft: prompt-lookup n-gram match.

    Deterministic and pure host work: find the most recent EARLIER
    occurrence of the trailing ``n``-gram of ``history`` (falling back
    to shorter grams down to 1) and propose the ``k`` tokens that
    followed it; pad by repeating the last token when the match runs
    off the end (or nothing matches).  Draft quality only moves the
    accept rate — greedy accept/reject makes the emitted trajectory
    independent of WHAT is proposed, so this needs no model at all.
    """
    h = np.asarray(history, dtype=np.int64)
    L = h.size
    if k <= 0:
        return np.zeros(0, dtype=np.int32)
    out = None
    for g in range(min(n, L - 1), 0, -1):
        tail = h[L - g:]
        # candidate gram ends at i + g (exclusive), strictly before L
        for i in range(L - g - 1, -1, -1):
            if np.array_equal(h[i:i + g], tail):
                out = h[i + g:i + g + k]
                break
        if out is not None:
            break
    if out is None:
        out = h[L - 1:]          # no match: repeat the last token
    prop = np.empty(k, dtype=np.int32)
    m = min(k, out.size)
    prop[:m] = out[:m]
    prop[m:] = int(out[m - 1]) if m else int(h[-1])
    return prop


def _served(model, program):
    """The model's own side of ``program`` (``serve_<program>``), or the
    typed refusal: a model that lacks a program is never served by a
    stand-in."""
    fn = getattr(model, "serve_" + program, None)
    if fn is None:
        raise UnsupportedProgramError(type(model).__name__, program)
    return fn


def prefill_program(model, state, *operands):
    """Pure prefill: full causal forward over the (padded) prompt.

    ``operands``: the cache pools the model declares, then ``tokens``
    ``[1, Tb]`` int32 (positions ``>= true_len`` are padding — their
    cache writes drop, and causality keeps them out of every valid
    position's attention), ``true_len`` and ``bt_row``.  The block is
    the model's (``serve_prefill``).  Returns ``(*pools, logits,
    *extras)`` with ``logits`` the fp32 ``[V]`` row at position
    ``true_len - 1`` and ``extras`` whatever the model counts for the
    engine's spans (nothing, for most).
    """
    *pools, tokens, true_len, bt_row = operands
    with bind_state(model, state):
        pools, logits, extras = _served(model, "prefill")(
            tuple(pools), tokens, true_len, bt_row)
        return (*pools, logits, *extras)


def prefix_prefill_program(model, state, *operands):
    """Pure SUFFIX prefill for a prefix-shared request (round 14).

    ``operands``: the pools, then ``tokens`` ``[1, Tb]`` int32 suffix
    tokens (positions ``>= true_len`` padding), ``true_len``, ``start``
    and ``bt_row``; suffix index ``t`` sits at absolute position
    ``start + t``, where ``start`` is the matched prefix length, and
    ``bt_row`` (``[N]``) covers the WHOLE context (shared prefix pages +
    the request's fresh suffix pages).  The model
    (``serve_suffix_prefill``) writes the suffix's entries through the
    offset writer and attends against the context read back through the
    block table: no attention and no projection is recomputed over the
    shared pages, which is the FLOP saving the prefix hit buys.
    Returns ``(*pools, logits, *extras)`` with ``logits`` the fp32
    ``[V]`` row at suffix position ``true_len - 1`` (the match is capped
    at ``prompt - 1`` tokens, so the first-generation logits always come
    from a live suffix position).
    """
    *pools, tokens, true_len, start, bt_row = operands
    with bind_state(model, state):
        pools, logits, extras = _served(model, "suffix_prefill")(
            tuple(pools), tokens, true_len, start, bt_row)
        return (*pools, logits, *extras)


def decode_program(model, state, *operands, mode, tp_mesh=None):
    """Pure decode step: one token per batch lane.

    ``operands``: the pools, then ``toks``/``pos`` ``[Bb]`` int32
    (``pos < 0`` marks an idle padding lane: its cache write drops and
    its attention context is empty) and ``bts`` ``[Bb, N]`` block
    tables.  The model (``serve_decode``) writes each lane's entry at
    ``pos`` then attends over ``[0, pos]`` through the block table.
    ``tp_mesh``: the tensor-parallel mesh — pools arrive sharded as the
    model declared.  Returns ``(*pools, logits [Bb, V] fp32, next_tok
    [Bb], *extras)``.
    """
    *pools, toks, pos, bts = operands
    with bind_state(model, state):
        pools, logits, extras = _served(model, "decode")(
            tuple(pools), toks, pos, bts, mode=mode, tp_mesh=tp_mesh)
        with observability.role("head"):      # the token pick
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (*pools, logits, nxt, *extras)


def spec_verify_program(model, state, *operands, tp_mesh=None):
    """Pure speculative VERIFY step: score K+1 tokens per lane in one
    dispatch (round 20).

    ``operands``: the pools, then ``toks`` ``[Bb, K1]`` int32 — lane
    ``b``'s pending token followed by its K draft proposals; token ``j``
    sits at absolute position ``start[b] + j`` — ``start`` ``[Bb]``
    int32 (``< 0`` = idle lane), ``n_valid`` ``[Bb]`` int32 (only the
    first ``n_valid[b]`` span slots write; lanes near their emit budget
    speculate short; surplus writes drop) and ``bts``.  The model
    (``serve_verify``) makes query ``j`` see exactly positions ``<=
    start + j``, i.e. the context a vanilla decode step at that
    position would see, which is why the returned argmax row ``g[b,
    j]`` equals what one-token decode WOULD have produced had tokens
    ``0..j`` been emitted one at a time.  The host then accepts the
    longest prefix where draft ``j+1`` equals ``g[j]`` and emits
    ``g[0..a]`` — up to K+1 tokens from one dispatch, bit-identical to
    vanilla greedy decode.  Returns ``(*pools, logits [Bb, K1, V] fp32,
    g [Bb, K1])``.
    """
    *pools, toks, start, n_valid, bts = operands
    with bind_state(model, state):
        pools, logits, _ = _served(model, "verify")(
            tuple(pools), toks, start, n_valid, bts, tp_mesh=tp_mesh)
        with observability.role("head"):
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (*pools, logits, g)


def next_tokens_program(prevs, sel):
    """The ``toks`` operand of a decode run, made on the device from the
    run before it (PR 46), for every pair of batch buckets in ONE
    program.  ``prevs`` holds one int32 vector a batch bucket, in the
    buckets' order: the run before's ``nxt`` in the place of its
    bucket, anything in the others.  ``sel`` is ``[B_max]`` int32: lane
    ``j`` takes element ``sel[j]`` of the vectors laid end to end where
    ``sel[j] >= 0`` (the token that run gave the lane at its old row),
    and the host-known token ``-1 - sel[j]`` otherwise (a lane admitted
    since: the first token its prefill gave; an idle lane: 0).  Returns
    the tokens cut to each bucket, in the buckets' order; the caller
    takes its own.  Rows move when a lane retires and buckets change,
    and a compile a pair of buckets cost the largest cell 29.7 s of
    set-up (PERF.md section 6, PR 45): this is one compile an engine."""
    flat = jnp.concatenate(prevs)
    toks = jnp.where(sel >= 0, flat[jnp.maximum(sel, 0)], -1 - sel)
    return tuple(toks[:p.shape[0]] for p in prevs)


class _Flight:
    """A decode run dispatched and not yet landed: its tokens (``nxt``)
    and what the model counted (``extras``) still on the device, and the
    requests it serves in row order (``rows``: a request's ``id()`` ->
    its row; request ids are the caller's and may come round again).
    ``pos`` (the run's position operand), ``step`` (its index) and
    ``ahead`` (whether it went behind a run in flight) are what the
    span that lands it says of it."""

    __slots__ = ("nxt", "extras", "lanes", "rows", "pos", "step", "ahead")

    def __init__(self, nxt, extras, lanes, pos, step, ahead):
        self.nxt, self.extras, self.lanes = nxt, extras, lanes
        self.pos, self.step, self.ahead = pos, step, ahead
        self.rows = {id(req): j for j, req in enumerate(lanes)}


class _AdmitDeferred(Exception):
    """Internal: this request cannot admit THIS step (e.g. the single
    disagg scratch pool is mid-chunk for another prompt) — requeue
    front-of-line and retry next step.  Never escapes the engine."""


def _bucket(n, buckets, what):
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{what} {n} exceeds the largest bucket "
                     f"{buckets[-1]}")


def _pow2_buckets(lo, hi):
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(out)


class ServingEngine:
    """Continuous-batching engine over any model with the serving
    interface (docs/serving.md): the model declares what a token leaves
    in the cache (``serve_cache_layers``, ``serve_cache_entry()``,
    ``serve_page_dtype``), its context limit (``serve_max_context``) and
    the dtype its parameters are held in (``serve_param_dtype``), and
    owns its block: ``serve_prefill`` / ``serve_suffix_prefill`` /
    ``serve_decode`` over per-layer views of the page pools.  The engine
    keeps the scheduling, the pages, the buckets and the spans.  A
    program a model lacks (``serve_verify`` for ``spec_k``,
    ``serve_pool_sharding`` for ``tp``) is refused at construction with
    :class:`~chainermn_tpu.serving.errors.UnsupportedProgramError`.

    A model whose layers keep their entries for different spans declares
    its cache by GROUPS of layers (``serve_cache_groups()``: ``(name,
    layers, entry, window)`` each, the group that keeps every position
    first; docs/serving.md).  Each group gets pools of its own page
    count and every sequence a block table a group, which the programs
    take stacked (``[groups, N]``, ``[groups, Bb, N]``).  ``num_pages``
    is the first group's; a window group is sized by
    :meth:`window_group_pages`: ``max_batch · (window / page_size + 1)
    + 4 · max_context / page_size`` pages, rounded up to 128 (every
    lane's window, one prompt in flight, and room for what the prefix
    trie alone still holds).  A group that keeps one entry a SEQUENCE
    (a recurrent layer's state: its span a
    :class:`~chainermn_tpu.serving.kv_cache.PerSequence`, declared after
    the groups that keep pages) gets slots, not pages, sized by
    :meth:`state_group_slots`: ``max_batch + 4 · ceil(max_context /
    stride)``, rounded up to 8 (every lane, and four prompts' worth of
    snapshots); its row of the stacked table carries slots: ``[live,
    source, snapshot 0, ...]`` for a prefill, ``[live]`` a lane for
    decode.  ``spec_k``, ``tp`` and ``disagg`` are refused for such a
    model.

    Greedy sampling (the serving bench's configuration); the paged/dense
    attention lowering is resolved ONCE at construction
    (``CHAINERMN_TPU_PAGED_ATTN``), as is the disaggregation mode
    (``CHAINERMN_TPU_SERVE_DISAGG``).

    ``prefix_cache``: copy-on-write prefix sharing (default on).
    ``disagg``: run full prefills on a separate prefill device/slice
    and ship finished pages into the decode pool (``None`` = the env
    knob; the default prefill device is the next device after the
    decode slice, degenerating to the same device on one-device hosts).
    ``tp``: shard the cache pools (and both programs) over a ``tp``-way
    mesh, along the axis the model declares (``serve_pool_sharding``:
    the head axis of K and V).
    ``spec_k``: speculative decoding — K draft tokens verified per
    sequence per decode dispatch (0 = vanilla one-token decode;
    ``CHAINERMN_TPU_SERVE_SPEC=off`` forces 0).  ``draft_model``: a
    small drafter with the same interface (same vocabulary; its cache
    pages are indexed by the SAME block tables, so it must accept the
    engine's page geometry); ``None`` = the n-gram self-draft.
    ``chunk_tokens``: chunked prefill — prompts whose unmatched
    remainder exceeds this admit in page-multiple chunks interleaved
    with decode steps (``None`` = off, one-shot prefill as before).
    ``chunk_budget``: max prefill tokens advanced per engine step
    (default ``chunk_tokens`` — one chunk per step).
    """

    def __init__(self, model, num_pages=256, page_size=16, max_batch=8,
                 max_context=256, page_dtype=None, max_queue=256,
                 scheduler=None, mode=None, eos_id=None,
                 prefix_cache=True, disagg=None, tp=1,
                 prefill_device=None, decode_device=None,
                 spec_k=0, draft_model=None, chunk_tokens=None,
                 chunk_budget=None):
        max_len = model.serve_max_context
        if max_context > max_len:
            raise ValueError(f"max_context={max_context} exceeds the "
                             f"model's max_len={max_len}")
        if page_dtype is None:
            page_dtype = model.serve_page_dtype
        self.model = model
        self.state = self._held_state(model)
        # a model whose layers keep their entries for different spans
        # declares its cache by groups of layers (the full group first);
        # any other has the one group it always had
        groups = model.serve_cache_groups() \
            if hasattr(model, "serve_cache_groups") else None
        self.cache_groups = len(groups) if groups else 0
        if groups is None:
            self.kv = PagedKVCache(model.serve_cache_layers, num_pages,
                                   page_size, model.serve_cache_entry(),
                                   dtype=page_dtype)
            self.allocator = BlockAllocator(num_pages, page_size)
        else:
            (_, n_layers, entry, window), *rest = groups
            if window is not None:
                raise ValueError("the first cache group keeps every "
                                 "position; it has no window")
            kinds = [isinstance(span, PerSequence) for *_, span in rest]
            if kinds != sorted(kinds):
                raise ValueError("the groups that keep pages come before "
                                 "those that keep one entry a sequence")
            sized = [(n, self.window_group_pages(w, page_size, max_batch,
                                                 max_context), e, w)
                     for _, n, e, w in rest
                     if not isinstance(w, PerSequence)]
            slotted = [(n, self.state_group_slots(span.stride, max_batch,
                                                  max_context), e,
                        span.stride)
                       for _, n, e, span in rest
                       if isinstance(span, PerSequence)]
            self.kv = PagedKVCache(
                n_layers, num_pages, page_size, entry, dtype=page_dtype,
                more=[(n, pages, e) for n, pages, e, _ in sized],
                states=[(n, slots, e) for n, slots, e, _ in slotted])
            self.allocator = BlockAllocator(
                num_pages, page_size,
                windows=[(pages, w) for _, pages, _, w in sized],
                states=[(slots, stride) for _, slots, _, stride in slotted])
            for _, _, _, stride in slotted:
                # [live, source] and a snapshot a stride of the longest
                # prompt ride in the group's row of the block table
                if 2 + -(-max_context // stride) > -(-max_context
                                                     // page_size):
                    raise ValueError(
                        f"snapshot stride {stride} leaves no room for a "
                        f"prompt's slots in a block table of "
                        f"{-(-max_context // page_size)} entries")
            if len(groups) > 1 and serve_disagg_mode(disagg):
                # one scratch pool and one ship: not written for a
                # second group
                raise UnsupportedProgramError(type(model).__name__,
                                              "page_ship")
        n_pools = len(self.kv.pools)
        self.scheduler = scheduler or RequestScheduler(max_queue=max_queue)
        self.max_batch = int(max_batch)
        self.max_context = int(max_context)
        self.n_block_entries = -(-self.max_context // page_size)
        self.mode = paged_attn_mode(mode)
        self.eos_id = eos_id
        self.prefix_cache = bool(prefix_cache)
        self.disagg = serve_disagg_mode(disagg)
        self.tp = int(tp)
        self.spec_k = serve_spec_k(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k={spec_k} must be >= 0")
        if self.spec_k:
            _served(model, "verify")    # typed refusal, before any compile
        self.draft_model = draft_model if self.spec_k else None
        self.chunk_tokens = int(chunk_tokens) if chunk_tokens else None
        if self.chunk_tokens is not None:
            if self.chunk_tokens % page_size:
                raise ValueError(
                    f"chunk_tokens={chunk_tokens} must be a multiple of "
                    f"page_size={page_size} (chunks end on page "
                    f"boundaries)")
            if self.chunk_tokens > self.max_context:
                raise ValueError(
                    f"chunk_tokens={chunk_tokens} exceeds "
                    f"max_context={max_context}")
        self.chunk_budget = int(chunk_budget) if chunk_budget \
            else (self.chunk_tokens or 0)
        # prefill buckets top out at the chunk size when chunking: any
        # prompt (or unmatched suffix) above the largest bucket routes
        # to the chunk state machine, so _bucket's ValueError becomes
        # unreachable for admitted work (the round-20 engine fix)
        prefill_cap = self.chunk_tokens or self.max_context
        self.prefill_buckets = _pow2_buckets(min(16, prefill_cap),
                                             prefill_cap)
        self.batch_buckets = _pow2_buckets(1, self.max_batch)
        self.transfer_buckets = _pow2_buckets(1, self.n_block_entries)
        self.running = []       # admission order, oldest first
        self.prefilling = []    # mid-chunk admissions, oldest first
        self.completed = []
        self.prefill_traces = 0
        self.prefix_prefill_traces = 0
        self.decode_traces = 0
        self.fork_traces = 0
        self.transfer_traces = 0
        self.spec_traces = 0
        self.chunk_traces = 0
        self.evictions = 0
        self.decode_steps = 0         # decode runs dispatched
        self.decode_steps_ahead = 0   # of them, behind a run in flight
        self.ahead_traces = 0
        self._flight = None           # the decode run not yet landed
        self._lowered = set()         # (program, pools, shapes) once run
        self._idle_prevs = None       # the gather's operands, once warm
        self.admissions = 0
        self.prefix_hits = 0
        self.prefix_tokens_matched = 0
        self.forks = 0
        self.transfers = 0
        self.transferred_page_bytes = 0
        self.spec_steps = 0
        self.spec_lane_steps = 0   # lane-dispatches: sum of batch sizes
        self.spec_proposed = 0     # over spec steps — the denominator
        self.spec_accepted = 0     # of accepted_tokens_per_dispatch
        self.spec_emitted = 0
        self.draft_dispatches = 0
        self.chunk_prefills = 0
        self.chunked_admissions = 0

        # draft cache pools: indexed by the SAME block tables as the
        # target pools (same page geometry), so draft pages ride the same
        # refcounted allocator — one accounting, one eviction story
        if self.draft_model is not None:
            d_max_len = self.draft_model.serve_max_context
            if d_max_len < self.max_context:
                raise ValueError(
                    f"draft_model max_len={d_max_len} below "
                    f"max_context={max_context}")
            self._draft_state = self._held_state(self.draft_model)
            self._kv_draft = PagedKVCache(
                self.draft_model.serve_cache_layers, num_pages, page_size,
                self.draft_model.serve_cache_entry(), dtype=page_dtype)
            # the draft's full-prompt prefill buckets are UNCAPPED by
            # chunking (the draft is small — one flash pass is cheaper
            # than teaching it the chunk machinery)
            self._draft_prefill_buckets = _pow2_buckets(
                min(16, self.max_context), self.max_context)

        devices = jax.devices()

        # -- tensor-parallel decode: pools laid out per shard (for K and
        # V the head axis of the tp mesh — the ulysses sharding), params
        # replicated over the mesh; both programs then compile under
        # GSPMD
        if self.tp > 1:
            if len(devices) < self.tp:
                raise ValueError(f"tp={self.tp} needs {self.tp} devices, "
                                 f"have {len(devices)}")
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            self._tp_mesh = Mesh(np.array(devices[:self.tp]), ("tp",))
            pool_sh = _served(model, "pool_sharding")(self._tp_mesh)
            self.kv.pools = [jax.device_put(p, pool_sh)
                             for p in self.kv.pools]
            self.state = jax.device_put(
                self.state, NamedSharding(self._tp_mesh, PartitionSpec()))
            # transferred page blocks land sharded the same way
            self._block_placement = pool_sh
            # a decode run leaves its tokens whole on every shard, and
            # the host's tokens are placed the same: a run that takes
            # them and a run that takes the output of the run before it
            # are then one executable
            self._toks_placement = NamedSharding(self._tp_mesh,
                                                 PartitionSpec())
        else:
            self._tp_mesh = None
            self._toks_placement = None
            self._block_placement = decode_device or devices[0]

        # -- disaggregation: a scratch pool + weight copy on the prefill
        # device; finished pages ship into the decode pool (device_put —
        # an ICI copy between slices on real pods), metered below
        if self.disagg:
            self._prefill_device = prefill_device or \
                devices[self.tp % len(devices)]
            if self.tp == 1:
                dd = decode_device or devices[0]
                self.kv.pools = [jax.device_put(p, dd)
                                 for p in self.kv.pools]
                self.state = jax.device_put(self.state, dd)
            self._kv_prefill = PagedKVCache(
                model.serve_cache_layers, self.n_block_entries, page_size,
                model.serve_cache_entry(), dtype=page_dtype)
            self._kv_prefill.pools = [
                jax.device_put(p, self._prefill_device)
                for p in self._kv_prefill.pools]
            self._state_prefill = jax.device_put(self.state,
                                                 self._prefill_device)
            # the scratch pool's identity block table: prefill always
            # writes pages 0..pages_for(L)-1 of the scratch pool
            self._scratch_bt = jax.device_put(
                jnp.arange(self.n_block_entries, dtype=jnp.int32),
                self._prefill_device)

        # donate the pools on real accelerators only: XLA then updates
        # pages in place; on cpu donation is ignored and merely warns.
        # Every program takes the state, then the pools the model
        # declared, then its operands, and returns the pools first
        real = jax.default_backend() == "tpu"
        donate = tuple(range(1, 1 + n_pools)) if real else ()
        donate0 = tuple(range(n_pools)) if real else ()

        def _prefill(state, *operands):
            self.prefill_traces += 1   # trace-time side effect only
            return prefill_program(self.model, state, *operands)

        def _prefix_prefill(state, *operands):
            self.prefix_prefill_traces += 1
            return prefix_prefill_program(self.model, state, *operands)

        def _decode(state, *operands):
            self.decode_traces += 1    # trace-time side effect only
            return decode_program(self.model, state, *operands,
                                  mode=self.mode, tp_mesh=self._tp_mesh)

        def _spec_verify(state, *operands):
            self.spec_traces += 1   # trace-time side effect only
            return spec_verify_program(self.model, state, *operands,
                                       tp_mesh=self._tp_mesh)

        def _chunk(state, *operands):
            # the chunk program IS the suffix-prefill program — the
            # chunk cursor rides the same offset writer — but with its
            # own jit identity so chunk compiles are counted (and
            # warmed) separately from prefix-hit suffix prefills
            self.chunk_traces += 1
            return prefix_prefill_program(self.model, state, *operands)

        def _draft_prefill(state, *operands):
            self.spec_traces += 1
            return prefill_program(self.draft_model, state, *operands)

        def _draft_decode(state, *operands):
            self.spec_traces += 1
            return decode_program(self.draft_model, state, *operands,
                                  mode=self.mode, tp_mesh=None)

        def _next_tokens(prevs, sel):
            self.ahead_traces += 1
            return next_tokens_program(prevs, sel)

        def _fork(*pools_src_dst):
            self.fork_traces += 1
            return copy_page(*pools_src_dst)

        def _extract(*pools_nb):
            self.transfer_traces += 1
            return tuple(p[:, :pools_nb[-1]] for p in pools_nb[:-1])

        def _insert(*pools_blocks_rows):
            self.transfer_traces += 1
            pools = pools_blocks_rows[:n_pools]
            blocks = pools_blocks_rows[n_pools:-1]
            return tuple(insert_pages(p, b, pools_blocks_rows[-1])
                         for p, b in zip(pools, blocks))

        self._prefill_fn = jax.jit(_prefill, donate_argnums=donate)
        self._prefix_prefill_fn = jax.jit(_prefix_prefill,
                                          donate_argnums=donate)
        self._decode_fn = jax.jit(_decode, donate_argnums=donate)
        self._spec_verify_fn = jax.jit(_spec_verify,
                                       donate_argnums=donate)
        self._chunk_fn = jax.jit(_chunk, donate_argnums=donate)
        self._draft_prefill_fn = jax.jit(_draft_prefill,
                                         donate_argnums=donate)
        self._draft_decode_fn = jax.jit(_draft_decode,
                                        donate_argnums=donate)
        self._next_tokens_fn = jax.jit(_next_tokens)
        self._fork_fn = jax.jit(_fork, donate_argnums=donate0)
        self._extract_fn = jax.jit(_extract, static_argnums=n_pools)
        self._insert_fn = jax.jit(_insert, donate_argnums=donate0)

    @staticmethod
    def window_group_pages(window, page_size, max_batch, max_context):
        """The pages a window group gets, sized from what the engine is
        given: every lane's window (``window / page_size + 1`` pages),
        one whole prompt in flight, and room for three more prompts'
        worth of pages that only the prefix trie holds, rounded up to
        128 pages: ``max_batch · (window / page_size + 1) + 4 ·
        max_context / page_size``.  A live sequence keeps at most
        ``window / page_size + 2`` pages outside its prefill, so the
        lanes can never exhaust the pool, and what the trie holds is
        given up before a lane is touched."""
        pages = max_batch * (window // page_size + 1) \
            + 4 * -(-max_context // page_size)
        return -(-pages // 128) * 128

    @staticmethod
    def state_group_slots(stride, max_batch, max_context):
        """The slots a state group gets: every lane's live state, and
        four prompts' worth of the snapshots a prefill leaves every
        ``stride`` tokens, rounded up to 8: ``max_batch + 4 ·
        ceil(max_context / stride)``.  What the trie alone holds is
        given up before an admission is refused."""
        slots = max_batch + 4 * -(-max_context // stride)
        return -(-slots // 8) * 8

    @staticmethod
    def _held_state(model):
        """The model's state as the engine holds it: its parameters in
        the dtype the model declares (``serve_param_dtype``; ``None``
        keeps them as loaded), cast in the model's own links one leaf at
        a time so that each leaf's old array is freed as it goes."""
        if model.serve_param_dtype is not None:
            cast_params(model, model.serve_param_dtype)
        return extract_state(model)

    def _run(self, fn, kv, state, *operands):
        """One compiled program over ``kv``'s pools: the pools it
        returns (donated, on an accelerator) are stored back, the rest
        of its outputs returned.  The first call of ``fn`` at operands
        of these shapes traces and lowers a program, whoever makes it
        (``warmup()``, or a caller that drives ``step()``): it is made
        with room on the interpreter's frame stack, without which a
        large program's lowering takes one second or twenty by where
        the caller's stack happens to end
        (``utils.compat.call_with_frame_room``)."""
        key = (fn, id(kv), *(getattr(o, "shape", ()) for o in operands))
        if key in self._lowered:
            out = fn(state, *kv.pools, *operands)
        else:
            self._lowered.add(key)
            out = call_with_frame_room(fn, state, *kv.pools, *operands)
        kv.pools = list(out[:len(kv.pools)])
        return out[len(kv.pools):]

    # -- ingress -------------------------------------------------------------

    def submit(self, request):
        """Queue a request (typed backpressure: QueueSaturatedError).
        Requests that could never fit are rejected here, typed, instead
        of livelocking admission later — the bound is the request's
        FULL eventual context (prompt + max_new_tokens): a request that
        merely *starts* inside the pool would grow until exhaustion,
        evict itself, fold its tokens into the prompt, and re-admit
        into the same wall forever (eviction can only free OTHER
        sequences' pages).  Conservative for eos-terminated requests by
        design: admission cannot know where eos lands — and
        conservative under prefix sharing too: the match is computed at
        ADMISSION (sharing at submit would pin live pages for the whole
        open-loop queue depth), so the fit check assumes zero hit."""
        total = request.prompt.size + request.max_new_tokens
        if total > self.max_context:
            raise ValueError(
                f"request needs {total} positions, engine "
                f"max_context={self.max_context}")
        if self.allocator.pages_for(total) > self.allocator.num_pages:
            raise PagePoolExhaustedError(
                self.allocator.pages_for(total),
                self.allocator.num_pages, self.allocator.num_pages)
        self.scheduler.submit(request)

    # -- internals -----------------------------------------------------------

    def _bt_row(self, seq_id, snapshots=()):
        """The sequence's block table as the programs take it: ``[N]``,
        or for a model that declared cache groups ``[groups, N]``, a row
        a group (a window group's released entries stay zero: they lie
        below every position its layers read).  A state group's row is
        slots: ``[live, source, snapshot 0, ...]``, the snapshot entries
        those of ``snapshots`` (a group: a slot each state the program's
        scan leaves, one beyond the pool where none is kept)."""
        row = np.zeros(self.n_block_entries, dtype=np.int32)
        table = self.allocator.block_table(seq_id)
        row[:len(table)] = table
        if not self.cache_groups:
            return row
        rows = np.zeros((self.cache_groups, self.n_block_entries),
                        dtype=np.int32)
        rows[0] = row
        n_windows = len(self.allocator.windows)
        for g in range(n_windows):
            table, low = self.allocator.window_table(seq_id, g)
            rows[1 + g, low:len(table)] = table[low:]
        for g in range(len(self.allocator.states)):
            slots = list(self.allocator.state_slots(seq_id, g))
            if snapshots:
                slots += snapshots[g]
            rows[1 + n_windows + g, :len(slots)] = slots
        return rows

    def _snapshot_slots(self, seq_id, start, size):
        """Reserve the snapshots a prefill program of ``size`` tokens at
        offset ``start`` is to fill: its scan, over the bucket the
        tokens are padded to, leaves state ``i`` after ``min((i + 1) ·
        stride, size)`` tokens, and the trie keeps those that fall on a
        stride of the prompt.  A group: the slot of each of the scan's
        states (one beyond the pool: not kept).  Raises
        :class:`PagePoolExhaustedError`."""
        states = self.allocator.states
        if not states:
            return ()
        bucket = _bucket(size, self.prefill_buckets, "prefill length")
        at = [[start + min((i + 1) * st.stride, size)
               for i in range(-(-bucket // st.stride))] for st in states]
        held = self.allocator.reserve_snapshots(
            seq_id, [p for ps in at for p in ps])
        # a position's slot goes to the first state that stands there
        return [[slots.pop(p, st.num_slots) for p in ps]
                for st, ps, slots in zip(states, at, held)]

    def _zero_bt(self, *lead):
        """An all-zero block table of the programs' shape (warm-up,
        idle lanes): ``[*lead, N]``, a group axis first where the model
        declared groups."""
        shape = lead + (self.n_block_entries,)
        if self.cache_groups:
            shape = (self.cache_groups,) + shape
        return np.zeros(shape, dtype=np.int32)

    # -- observability (ISSUE 14) -------------------------------------------

    @staticmethod
    def _req_tid(req):
        """Synthetic per-request trace track: request lifecycle spans
        (queue wait → prefill → finish) overlap OTHER requests' spans
        in time, so they cannot share one thread's B/E stack — each
        request gets its own Chrome ``tid`` lane (the merged trace then
        shows one swimlane per request under the engine's rank).

        Request ids are caller-supplied and only ever used as dict keys
        elsewhere, so non-integer ids are legal — they map onto a
        deterministic crc32 lane (PYTHONHASHSEED-independent)."""
        rid = req.request_id
        if isinstance(rid, int):
            return 1 + rid
        import zlib
        return 1 + (zlib.crc32(str(rid).encode()) & 0x7FFFFFFF)

    def _obs_admitted(self, req, wait_s, readmit):
        """Queue-wait attribution at admission: a retroactive span on
        the request's lane (duration measured on the ENGINE clock —
        exact; absolute placement is the tracer's) plus the per-tenant
        queue-wait histogram the scheduler-health satellite commits.

        A RE-admission (evicted request re-entering) measures from the
        EVICTION'S requeue stamp, not the original arrival — the
        original window was already spanned (re-measuring from arrival
        would overlap it on the lane) and the prior RUNNING period is
        decode time, not queue wait."""
        tags = {"tenant": req.tenant, "request": req.request_id,
                "prompt": int(req.prompt.size)}
        if readmit:
            tags["readmit"] = True
        observability.complete("serve/queue_wait", wait_s, tags=tags,
                               tid=self._req_tid(req))
        observability.registry().histogram(
            "chainermn_tpu_serving_queue_wait_ms",
            help="admission queue wait per request (ms)").observe(
            wait_s * 1e3, tenant=req.tenant)

    def _obs_queue_depths(self):
        queues = getattr(self.scheduler, "_queues", None)
        if queues is None:   # a custom scheduler without tenant queues
            return
        gauge = observability.registry().gauge(
            "chainermn_tpu_serving_queue_depth",
            help="pending requests per tenant at the last decode step")
        for tenant in list(queues):
            gauge.set(self.scheduler.pending(tenant), tenant=tenant)

    def _record_token(self, req, tok, now):
        req.tokens.append(int(tok))
        req.token_times.append(now)
        if req.first_token_time is None:
            req.first_token_time = now

    def _finished(self, req):
        if len(req.tokens) >= req.max_new_tokens:
            return True
        return self.eos_id is not None and req.tokens \
            and req.tokens[-1] == self.eos_id

    def _retire(self, req, now):
        self.allocator.free(req.request_id)
        self.running.remove(req)
        req.finish_time = now
        self.completed.append(req)
        if observability.ring_enabled():
            observability.instant("serve/finish",
                                  tags={"tenant": req.tenant,
                                        "request": req.request_id,
                                        "tokens": len(req.tokens)},
                                  tid=self._req_tid(req))

    def _evict(self, req, now=None):
        """Preemption: free pages (refcount-aware — shared pages stay
        alive through their other holders), fold generated tokens into
        the prompt, re-queue front-of-line (recompute on re-admit).
        ``now`` stamps the requeue instant so the re-admission's queue
        wait measures the re-queue dwell, not the running period.

        A MID-CHUNK victim (round 20) frees its already-written chunk
        pages the same way — the scheduler's requeue resets its chunk
        cursor, so re-admission restarts from chunk 0 with no page
        leaked and no stale cursor (the scheduler-fix satellite).

        A victim is never in the decode run in flight (its newest token
        would be unknown to the host): the capacity pass lands that run
        before it picks one, and a prompt mid-chunk is in none."""
        assert self._flight is None or id(req) not in self._flight.rows
        self.allocator.free(req.request_id)
        if req in self.running:
            self.running.remove(req)
        else:
            self.prefilling.remove(req)
        req.requeue_time = now
        self.scheduler.requeue_front(req)
        self.evictions += 1
        if observability.ring_enabled():
            observability.instant("serve/evict",
                                  tags={"tenant": req.tenant,
                                        "request": req.request_id},
                                  tid=self._req_tid(req))
            observability.registry().counter(
                "chainermn_tpu_serving_evictions_total",
                help="running sequences preempted for pool pages").inc(
                1, tenant=req.tenant)

    def _run_fork(self, src, dst):
        """Copy-on-write page copy, in-graph (traced indices: every
        fork reuses the one compiled program)."""
        self.kv.pools = list(self._fork_fn(
            *self.kv.pools, jnp.int32(src), jnp.int32(dst)))
        self.forks += 1
        if observability.ring_enabled():
            observability.instant("serve/fork",
                                  tags={"src": int(src), "dst": int(dst)})
            observability.registry().counter(
                "chainermn_tpu_serving_forks_total",
                help="copy-on-write page forks").inc(1)

    def _run_prefix_prefill(self, req, L, matched, snapshots=()):
        """Prefix HIT: prefill only the unmatched suffix, against the
        decode pool (the shared pages live there — and on the disagg
        split this is exactly the work the hit keeps OFF the prefill
        slice).  A state group's layers start from the snapshot the hit
        gave (copied inside the program: the sequence's hold on it ends
        here)."""
        Ts = L - matched
        Tb = _bucket(Ts, self.prefill_buckets, "suffix length")
        tokens = np.zeros((1, Tb), dtype=np.int32)
        tokens[0, :Ts] = req.prompt[matched:]
        out = self._run(
            self._prefix_prefill_fn, self.kv, self.state,
            jnp.asarray(tokens), np.int32(Ts), np.int32(matched),
            jnp.asarray(self._bt_row(req.request_id, snapshots)))
        self.allocator.restored(req.request_id)
        return out

    def _run_disagg_prefill(self, req, L):
        """Prefix MISS on the disagg split: the full flash prefill runs
        on the PREFILL device against the scratch pool (identity block
        table), then the finished pages ship into the decode pool —
        bucketed page-count block, ``device_put`` across the slice
        boundary (an ICI copy on real pods), drop-fenced scatter on
        arrival — metered by ``transferred_page_bytes``."""
        Tb = _bucket(L, self.prefill_buckets, "prompt length")
        tokens = np.zeros((1, Tb), dtype=np.int32)
        tokens[0, :L] = req.prompt
        out = self._run(self._prefill_fn, self._kv_prefill,
                        self._state_prefill, jnp.asarray(tokens),
                        np.int32(L), self._scratch_bt)
        self._ship_pages(req, L)
        return out

    def _ship_pages(self, req, L):
        """Ship the first ``pages_for(L)`` scratch-pool pages into the
        decode pool at the request's allocated page ids (the disagg
        transfer leg, shared by one-shot and chunked prefills — a
        chunked prompt ships ONCE, after its last chunk)."""
        n_pages = self.allocator.pages_for(L)
        nb = _bucket(n_pages, self.transfer_buckets, "transfer pages")
        blocks = [jax.device_put(b, self._block_placement) for b in
                  self._extract_fn(*self._kv_prefill.pools, nb)]
        rows = np.full(nb, self.kv.num_pages, dtype=np.int32)
        rows[:n_pages] = self.allocator.block_table(
            req.request_id)[:n_pages]
        self.kv.pools = list(self._insert_fn(
            *self.kv.pools, *blocks, jnp.asarray(rows)))
        shipped = nb * self.kv.n_layers * self.kv.page_bytes
        self.transferred_page_bytes += shipped
        self.transfers += 1
        if observability.ring_enabled():
            observability.instant("serve/page_transfer",
                                  tags={"request": req.request_id,
                                        "pages": int(nb),
                                        "bytes": int(shipped)},
                                  tid=self._req_tid(req))
            observability.registry().counter(
                "chainermn_tpu_serving_transferred_page_bytes_total",
                help="KV page bytes shipped prefill slice -> decode "
                     "pool").inc(shipped)

    def _admit(self, req, clock):
        """Pages + prefill + first token.  Raises PagePoolExhaustedError
        (allocator untouched — a partial share is rolled back) when the
        pool cannot hold the prompt.

        Prefix sharing happens HERE, not at submit: only sequences live
        at admission can provide pages, and sharing earlier would pin
        pool pages for the whole queue depth.  The match is capped at
        ``L - 1`` so prefill always has >= 1 suffix token to produce
        the first-generation logits; a match ending mid-page forks that
        page (copy-on-write) before the suffix's first write."""
        L = int(req.prompt.size)
        sid = req.request_id
        t_admit = clock()
        matched = 0
        chunked = False
        prompt_t = tuple(int(t) for t in req.prompt) \
            if self.prefix_cache else ()
        if self.prefix_cache and L > 1:
            pages, matched, n_full, partial = \
                self.allocator.match_prefix(prompt_t, L - 1)
            if matched:
                chunked = self.chunk_tokens is not None \
                    and (L - matched) > self.chunk_tokens
                # all HOST-side allocation first (each call atomic, the
                # composite rolled back below), the device page copy
                # only once the admission cannot fail — a rollback must
                # not burn a copy or inflate the forks counter.  A
                # chunked admission reserves only its FIRST chunk's
                # pages (the point of chunking: a 16k prompt does not
                # grab 16k positions of pool up front)
                self.allocator.share(sid, pages)
                old = new = None
                try:
                    if partial:
                        old, new = self.allocator.fork(sid, n_full)
                    self.allocator.ensure(
                        sid, (matched + self.chunk_tokens) if chunked
                        else L + 1)            # +1: first decode
                except PagePoolExhaustedError:
                    self.allocator.free(sid)   # roll the share back
                    raise
                if new is not None and old != new:
                    self._run_fork(old, new)
        if not matched:
            chunked = self.chunk_tokens is not None \
                and L > self.chunk_tokens
            if chunked and self.disagg \
                    and any(r._chunk_scratch for r in self.prefilling):
                # ONE scratch pool on the prefill slice: a second
                # prefix-miss chunk stream would interleave into it —
                # defer (prefix-HIT chunk streams run against the
                # decode pool and admit freely)
                raise _AdmitDeferred()
            self.allocator.ensure(
                sid, self.chunk_tokens if chunked else L + 1)
        # the slots a state group's prefill leaves its snapshots in:
        # the last host-side allocation, rolled back with the rest
        snapshots = ()
        if not chunked:
            try:
                snapshots = self._snapshot_slots(sid, matched, L - matched)
            except PagePoolExhaustedError:
                self.allocator.free(sid)
                raise
        # queue-wait accounting (always — the bench reads it trace-off):
        # this admission's wait is arrival → now, or requeue → now after
        # an eviction (the prior RUNNING period is decode time, not
        # queue wait); the request accumulates the sum over admissions
        readmit = req.requeue_time is not None   # stamped by _evict
        wait_s = max(0.0, t_admit - (req.requeue_time if readmit
                                     else req.arrival_time))
        req.queue_wait_s += wait_s
        # lazy tag construction: the conditional expressions below keep
        # the trace-off path free of per-admission dict/lane-id work
        # (the module's near-zero-cost-off contract).  The request's
        # lane, the retroactive queue-wait span and the registry are the
        # ring's; a profiler session alone takes the span tags only
        obs_on = observability.enabled()
        ring_on = observability.ring_enabled()
        rtid = self._req_tid(req) if ring_on else None
        if ring_on:
            self._obs_admitted(req, wait_s, readmit)
        if chunked:
            # chunk-admitted: the prompt enters the chunk state machine
            # (cursor at the matched prefix; chunks advance in step()'s
            # chunk pass under the per-step budget).  No logits, no
            # first token, no prefix registration yet — those happen at
            # the LAST chunk.  The hit stats book now: the shared pages
            # are held from here on.
            req._chunk_pos = matched
            req._chunk_scratch = self.disagg and not matched
            if matched:
                self.prefix_hits += 1
                self.prefix_tokens_matched += matched
            req.admit_time = t_admit
            req.requeue_time = None   # consumed: next eviction re-stamps
            self.chunked_admissions += 1
            self.prefilling.append(req)
            if ring_on:
                observability.instant(
                    "serve/chunk_admit",
                    tags={"request": sid, "prompt": L,
                          "matched": matched}, tid=rtid)
            return
        # one request's spans share ``request``; ``wait_ms`` is this
        # admission's queue wait.  Each span runs to the first token on
        # the host, so the wait for the prefill's result lies inside it
        tags = {"request": sid, "prompt": L, "matched": matched,
                "wait_ms": wait_s * 1e3} if obs_on else None
        if obs_on and matched and self.allocator.states:
            tags["restored"] = matched    # the snapshot's position
        req.admit_time = t_admit
        req.requeue_time = None   # consumed: next eviction re-stamps
        if matched:
            with observability.span("serve/suffix_prefill", tags=tags,
                                    tid=rtid) as sp:
                logits, *extras = self._run_prefix_prefill(
                    req, L, matched, snapshots)
                self.prefix_hits += 1
                self.prefix_tokens_matched += matched
                self._complete_admission(req, logits, clock, prompt_t)
                self._set_model_stats(sp, extras)
        elif self.disagg:
            if obs_on:
                tags["disagg"] = True
            with observability.span("serve/prefill", tags=tags,
                                    tid=rtid) as sp:
                logits, *extras = self._run_disagg_prefill(req, L)
                self._complete_admission(req, logits, clock, prompt_t)
                self._set_model_stats(sp, extras)
        else:
            with observability.span("serve/prefill", tags=tags,
                                    tid=rtid) as sp:
                Tb = _bucket(L, self.prefill_buckets, "prompt length")
                tokens = np.zeros((1, Tb), dtype=np.int32)
                tokens[0, :L] = req.prompt
                logits, *extras = self._run(
                    self._prefill_fn, self.kv, self.state,
                    jnp.asarray(tokens), np.int32(L),
                    jnp.asarray(self._bt_row(sid, snapshots)))
                self._complete_admission(req, logits, clock, prompt_t)
                self._set_model_stats(sp, extras)

    def _set_model_stats(self, sp, extras):
        """What the model counted inside a program (``extras``: device
        values its ``serve_*`` returned beside the logits) as stats of
        the span around it, through the model's own reading of them
        (``serve_span_stats``).  Read only while a span records: with
        tracing off the counts stay on the device, unfetched."""
        if extras and observability.enabled():
            sp.set(**self.model.serve_span_stats(
                *(np.asarray(e) for e in extras)))

    def _complete_admission(self, req, logits, clock, prompt_t):
        """The bookkeeping shared by one-shot and LAST-chunk admission:
        register the prefix, prefill the draft model's pools (its pages
        are the same block tables), take the first token from the
        prefill logits, and join the running batch."""
        sid = req.request_id
        self.admissions += 1
        if self.prefix_cache:
            self.allocator.register_prefix(sid, prompt_t)
        # the prompt is written and registered: from here the sequence
        # holds its window alone (a no-op without window groups)
        self.allocator.slide(sid, int(req.prompt.size))
        if self.draft_model is not None:
            self._run_draft_prefill(req)
        tok = int(np.asarray(jnp.argmax(logits)))
        req._ctx = int(req.prompt.size)  # positions whose KV is written
        t = clock()
        self._record_token(req, tok, t)
        self.running.append(req)
        if self._finished(req):
            self._retire(req, t)

    def _run_draft_prefill(self, req):
        """Write the DRAFT model's KV for the whole prompt through the
        request's block tables (one small flash pass; logits
        discarded — the first token always comes from the target).
        Positions inside shared prefix pages rewrite bytes the provider
        already wrote — same draft model, same tokens, same positions,
        so the bytes are identical and the refcounts never notice."""
        L = int(req.prompt.size)
        Tb = _bucket(L, self._draft_prefill_buckets, "draft prompt")
        tokens = np.zeros((1, Tb), dtype=np.int32)
        tokens[0, :L] = req.prompt
        self._run(self._draft_prefill_fn, self._kv_draft,
                  self._draft_state, jnp.asarray(tokens), np.int32(L),
                  jnp.asarray(self._bt_row(req.request_id)))
        req._draft_ctx = L

    def _run_chunk(self, req, startp, size, final, clock, snapshots=()):
        """One chunk of a chunked prefill: ``size`` prompt tokens at
        cursor ``startp`` through the chunk program (the offset-writer
        suffix shape; chunk 0 is ``start=0``).  Prefix-miss chunks on
        the disagg split run on the PREFILL slice against the scratch
        pool (identity block table) and ship once, after the last
        chunk; everything else runs against the decode pool."""
        sid = req.request_id
        L = int(req.prompt.size)
        Tb = _bucket(size, self.prefill_buckets, "chunk length")
        tokens = np.zeros((1, Tb), dtype=np.int32)
        tokens[0, :size] = req.prompt[startp:startp + size]
        scratch = getattr(req, "_chunk_scratch", False)
        if scratch:
            logits, *_ = self._run(
                self._chunk_fn, self._kv_prefill, self._state_prefill,
                jnp.asarray(tokens), np.int32(size), np.int32(startp),
                self._scratch_bt)
        else:
            logits, *_ = self._run(
                self._chunk_fn, self.kv, self.state,
                jnp.asarray(tokens), np.int32(size), np.int32(startp),
                jnp.asarray(self._bt_row(sid, snapshots)))
            self.allocator.restored(sid)    # the next chunk: its own slot
        self.chunk_prefills += 1
        req._chunk_pos = startp + size
        if final:
            if scratch:
                self._ship_pages(req, L)
            self.prefilling.remove(req)
            prompt_t = tuple(int(t) for t in req.prompt) \
                if self.prefix_cache else ()
            self._complete_admission(req, logits, clock, prompt_t)
        else:
            self.allocator.slide(sid, startp + size)

    def _advance_chunks(self, clock):
        """The chunk pass of one engine step: advance mid-chunk
        prompts, oldest first, under the per-step token budget (the
        interleave that keeps decode latency flat while long prompts
        stream in).  A request whose next chunk cannot get pages
        STALLS — it keeps the pages it has and retries next step;
        admission-flavored work never preempts running sequences.  The
        one exception is the all-prefilling deadlock (no running work,
        two-plus mid-chunk prompts splitting a full pool): the
        YOUNGEST other mid-chunk victim is evicted so the oldest can
        finish.  Returns prefill tokens advanced."""
        budget = self.chunk_budget
        progressed = 0
        obs_on = observability.enabled()
        for req in list(self.prefilling):
            if budget <= 0:
                break
            L = int(req.prompt.size)
            while budget > 0 and req in self.prefilling:
                startp = req._chunk_pos
                remaining = L - startp
                size = min(self.chunk_tokens, remaining)
                final = size == remaining
                try:
                    self.allocator.ensure(
                        req.request_id,
                        startp + size + (1 if final else 0))
                    snapshots = self._snapshot_slots(req.request_id,
                                                     startp, size)
                except PagePoolExhaustedError:
                    break   # stall: keep pages, retry next step
                with observability.span(
                        "serve/chunk_prefill",
                        tags={"request": req.request_id,
                              "start": startp, "chunk": size,
                              "final": final} if obs_on else None,
                        tid=self._req_tid(req)
                        if observability.ring_enabled() else None):
                    self._run_chunk(req, startp, size, final, clock,
                                    snapshots)
                budget -= size
                progressed += size
        if not progressed and not self.running \
                and len(self.prefilling) > 1:
            # deadlock guard: evict the youngest OTHER mid-chunk prompt
            # (they hold pages and produced no tokens — least work
            # lost); the oldest inherits the freed pages next step
            victim = self.scheduler.pick_victim(
                [], self.allocator, prefilling=self.prefilling[1:])
            self._evict(victim, clock())
        return progressed

    def capacity_multiplier(self):
        """Effective-capacity multiplier prefix sharing is buying right
        now: logical pages (what an unshared pool would hold for the
        same residency) over distinct physical pages.  1.0 when nothing
        is shared."""
        used = self.allocator.used_pages
        return self.allocator.logical_pages() / used if used else 1.0

    def _spec_nv(self, req):
        """Valid span length for this lane's verify step: the pending
        token plus at most K drafts, clamped so the lane never emits
        past its ``max_new_tokens`` budget — which (by the submit-time
        fit bound) also keeps every speculative write inside
        ``max_context`` and inside pages the capacity pass ensured."""
        r = req.max_new_tokens - len(req.tokens)   # >= 1 while running
        return 1 + min(self.spec_k, r - 1)

    def _propose_drafts(self, nv):
        """K draft tokens per running lane: the n-gram self-draft (pure
        host), or the draft model — one conditional catch-up dispatch
        (a fully-accepted lane's draft counter trails the target by
        exactly one position) followed by K single-token draft decode
        dispatches through the SAME block tables.  Draft writes land
        only at positions the capacity pass already ensured; rejected
        draft KV rewinds by counter exactly like the target's."""
        K = self.spec_k
        n = len(self.running)
        if self.draft_model is None:
            drafts = np.zeros((n, K), dtype=np.int32)
            for j, req in enumerate(self.running):
                hist = np.concatenate(
                    [np.asarray(req.prompt, np.int64),
                     np.asarray(req.tokens, np.int64)])
                drafts[j] = ngram_propose(hist, K)
            return drafts
        Bb = _bucket(n, self.batch_buckets, "batch")
        bts = np.zeros((Bb, self.n_block_entries), dtype=np.int32)
        for j, req in enumerate(self.running):
            bts[j] = self._bt_row(req.request_id)
        bts_j = jnp.asarray(bts)
        # catch-up: lanes at gap 1 write the history token the target
        # accepted past them (everyone else idles at pos -1, dropped)
        cu_tok = np.zeros(Bb, dtype=np.int32)
        cu_pos = np.full(Bb, -1, dtype=np.int32)
        any_gap = False
        for j, req in enumerate(self.running):
            if req._draft_ctx == req._ctx - 1:
                any_gap = True
                cu_pos[j] = req._ctx - 1
                cu_tok[j] = req.tokens[-2] if len(req.tokens) >= 2 \
                    else int(req.prompt[-1])
                req._draft_ctx = req._ctx
        if any_gap:
            self._run(self._draft_decode_fn, self._kv_draft,
                      self._draft_state, jnp.asarray(cu_tok),
                      jnp.asarray(cu_pos), bts_j)
            self.draft_dispatches += 1
        drafts = np.zeros((n, K), dtype=np.int32)
        cur = np.zeros(Bb, dtype=np.int32)
        for j, req in enumerate(self.running):
            cur[j] = req.tokens[-1]
        for i in range(K):
            pos = np.full(Bb, -1, dtype=np.int32)
            live = False
            for j, req in enumerate(self.running):
                if i < nv[j] - 1:
                    pos[j] = req._ctx + i
                    live = True
            if not live:
                break
            _, nxt, *_ = self._run(
                self._draft_decode_fn, self._kv_draft, self._draft_state,
                jnp.asarray(cur), jnp.asarray(pos), bts_j)
            self.draft_dispatches += 1
            nxt = np.asarray(nxt)
            keep = pos >= 0
            drafts[:, i][keep[:n]] = nxt[:n][keep[:n]]
            cur = np.where(keep, nxt, cur).astype(np.int32)
        for j, req in enumerate(self.running):
            # positions ctx .. ctx+nv-2 now hold draft KV; acceptance
            # rewinds this to min(draft_ctx, new ctx) after the verify
            req._draft_ctx = req._ctx + max(0, int(nv[j]) - 1)
        return drafts

    def warmup(self):
        """Compile EVERY bucketed program up front: one dummy prefill
        per prompt bucket (``true_len=0`` — every page write drops; on
        the disagg split these run on the prefill device against the
        scratch pool), one dummy suffix prefill per bucket plus the
        fork-copy program (prefix sharing), one extract+insert pair per
        transfer page bucket (disagg — padding rows, every scatter
        drops), and one dummy decode per batch bucket (all lanes idle).
        Pool contents are unchanged; afterwards joins/leaves/forks/
        transfers never retrace (the benchmark's serve driver refuses a
        window that traced).  Round 20 grids ride along: one
        chunk program per prefill bucket (per pool shape on the disagg
        split), one spec verify per batch bucket (all lanes idle,
        every span write dropped), and the draft model's prefill +
        decode grids — afterwards ``spec_traces``/``chunk_traces``
        stay frozen across joins, forks, evictions and accept-length
        swings (the round-20 retrace pin).  PR 46: the gather that hands
        one decode run's tokens to the next, ONE small program for
        every pair of batch buckets (an engine that is never warmed
        compiles it at its first decode run)."""
        zero_row = jnp.asarray(self._zero_bt())

        def idle(Bb):
            return (jnp.zeros(Bb, jnp.int32), jnp.full(Bb, -1, jnp.int32),
                    jnp.asarray(self._zero_bt(Bb)))

        for Tb in self.prefill_buckets:
            empty = (jnp.zeros((1, Tb), jnp.int32), np.int32(0))
            if self.disagg:
                self._run(self._prefill_fn, self._kv_prefill,
                          self._state_prefill, *empty, self._scratch_bt)
            else:
                self._run(self._prefill_fn, self.kv, self.state, *empty,
                          zero_row)
        if self.disagg:
            for nb in self.transfer_buckets:
                blocks = [jax.device_put(b, self._block_placement)
                          for b in self._extract_fn(
                              *self._kv_prefill.pools, nb)]
                rows = jnp.full(nb, self.kv.num_pages, jnp.int32)
                self.kv.pools = list(self._insert_fn(
                    *self.kv.pools, *blocks, rows))
        if self.prefix_cache:
            for Tb in self.prefill_buckets:
                self._run(self._prefix_prefill_fn, self.kv, self.state,
                          jnp.zeros((1, Tb), jnp.int32), np.int32(0),
                          np.int32(0), zero_row)
            # the fork-copy program: src == dst == 0 is a self-copy
            # (contents unchanged); indices are traced, so this one
            # compile serves every fork
            self.kv.pools = list(self._fork_fn(
                *self.kv.pools, jnp.int32(0), jnp.int32(0)))
        if self.chunk_tokens is not None:
            for Tb in self.prefill_buckets:
                empty = (jnp.zeros((1, Tb), jnp.int32), np.int32(0),
                         np.int32(0))
                self._run(self._chunk_fn, self.kv, self.state, *empty,
                          zero_row)
                if self.disagg:
                    # scratch-pool chunk shape (prefix-miss chunks run
                    # on the prefill slice): distinct pool dims mean a
                    # distinct compile — warm it too
                    self._run(self._chunk_fn, self._kv_prefill,
                              self._state_prefill, *empty,
                              self._scratch_bt)
        for Bb in self.batch_buckets:
            _, nxt, *_ = self._run(
                self._decode_fn, self.kv, self.state,
                self._host_toks(np.zeros(Bb, np.int32)), *idle(Bb)[1:])
            if self._may_run_ahead():
                # a run dispatched ahead takes its tokens as the run
                # before it left them on the device (the same
                # executable: ``_host_toks``), or through the gather
                self._warm_next_tokens(nxt)
            if self.spec_k:
                _, nxt = self._run(
                    self._spec_verify_fn, self.kv, self.state,
                    jnp.zeros((Bb, self.spec_k + 1), jnp.int32),
                    jnp.full(Bb, -1, jnp.int32), jnp.zeros(Bb, jnp.int32),
                    jnp.zeros((Bb, self.n_block_entries), jnp.int32))
        if self.draft_model is not None:
            for Tb in self._draft_prefill_buckets:
                self._run(self._draft_prefill_fn, self._kv_draft,
                          self._draft_state, jnp.zeros((1, Tb), jnp.int32),
                          np.int32(0), zero_row)
            for Bb in self.batch_buckets:
                _, nxt, *_ = self._run(self._draft_decode_fn,
                                       self._kv_draft, self._draft_state,
                                       *idle(Bb))
        np.asarray(nxt)  # sync: compiles really happened

    # -- the step loop -------------------------------------------------------

    def step(self, now=None):
        """One continuous-batching step: an admission pass (fair
        rotation, open-loop eligibility by ``now``) then ONE decode step
        over the running batch: when call ``k`` returns, the tokens of
        decode run ``k`` are recorded and stamped.  Returns step stats.

        One decode run is kept in flight (PR 46): a call dispatches the
        run AFTER the one it lands, its tokens taken on the device, then
        fetches and records the run the call before it dispatched, so
        the device has the next run queued while the host works.  A call
        that finds nothing in flight dispatches two runs and lands the
        first.  A lane whose last token (by ``max_new_tokens``) is in
        flight is left out of the run ahead; a finish by ``eos_id`` is
        found one run late, and the spare run's token for that lane is
        dropped unrecorded.  A request admitted in this call joins the
        run this call dispatches.  ``spec_k``, a prompt mid-chunk and
        the disaggregated split keep the order dispatch, fetch, record;
        a pool that runs dry lands the run in flight before it picks a
        victim.

        ``now=None`` (the bench's real-time mode) timestamps each token
        at its actual production instant (after the device fetch); a
        pinned ``now`` (deterministic tests / simulated clocks) stamps
        everything in this step with that value."""
        clock = time.monotonic if now is None else (lambda: now)
        with observability.span("serve/step") as sp:
            stats = self._step(clock)
            if observability.enabled():
                a = self.allocator
                sp.set(running=stats["running"], used_pages=a.used_pages,
                       num_pages=a.num_pages, **a.group_stats())
        return stats

    def _may_run_ahead(self):
        """Whether this engine ever dispatches a decode run behind one
        in flight: not with ``spec_k`` (the accepted length decides the
        next positions) and not on the disaggregated split (the ship
        crosses devices, which no one stream orders)."""
        return not (self.spec_k or self.disagg)

    def _last_in_flight(self, req):
        """The run in flight brings ``req``'s last token (it finishes by
        ``max_new_tokens`` when that run lands): no later run has it."""
        f = self._flight
        return f is not None and id(req) in f.rows \
            and len(req.tokens) + 1 >= req.max_new_tokens

    def _next_lanes(self):
        """The lanes of the next decode run to dispatch."""
        return [req for req in self.running
                if not self._last_in_flight(req)]

    def _secure(self, req, need=1):
        """Secure the pages of ``req``'s next ``need`` positions and let
        its windows slide (``PagePoolExhaustedError`` where the pool has
        not enough)."""
        self.allocator.ensure(req.request_id, req._ctx + need)
        self.allocator.slide(req.request_id, req._ctx)

    def _try_secure_all(self, lanes):
        """Secure every lane's next position, as the capacity pass does,
        but from what the pool has left: no eviction.  False where it
        has not enough."""
        try:
            for req in lanes:
                self._secure(req)
        except PagePoolExhaustedError:
            return False
        return True

    def drop_in_flight(self):
        """Forget the decode run in flight, its tokens unrecorded (the
        sequences it serves are leaving this engine: a reroute
        recomputes them from the tokens already recorded)."""
        self._flight = None

    def _step(self, clock):
        stats = {"admitted": 0}
        evicted_before = self.evictions
        landed = None    # lanes of the run this call landed
        # capacity FIRST: secure this step's token page(s) for every
        # running sequence (evicting youngest-first when the pool runs
        # dry) BEFORE admitting anyone — admission into pages the
        # running batch is about to need would get the just-prefilled
        # newcomer evicted in the same step, burning its whole prefill.
        # Speculative decode secures the whole verify SPAN (up to K+1
        # positions); mid-chunk prompts are eviction candidates too —
        # preferred victims, in fact: they hold pages and have produced
        # zero tokens.  ``_ctx`` counts the run in flight (it advances
        # at dispatch), so the page secured is the one the run
        # dispatched in THIS call writes; a lane whose last token is in
        # flight needs none
        with observability.span("serve/capacity"):
            i = 0
            while i < len(self.running):
                req = self.running[i]
                if self._last_in_flight(req):
                    i += 1
                    continue
                try:
                    self._secure(req, self._spec_nv(req) if self.spec_k
                                 else 1)
                    i += 1
                except PagePoolExhaustedError:
                    if self._flight is not None:
                        # a victim folds its tokens into its prompt:
                        # land the run in flight (every running lane is
                        # in it) before one is picked, then go on as
                        # the synchronous order does; lanes may have
                        # retired, so the pass starts over
                        landed = self._land(clock)
                        i = 0
                        continue
                    # refcount-aware victim choice: a victim must FREE
                    # something (EvictionStalledError otherwise — the
                    # prefix-sharing livelock guard)
                    victim = self.scheduler.pick_victim(
                        self.running, self.allocator,
                        prefilling=self.prefilling)
                    self._evict(victim, clock())
                    # victim may be req: the slot under scrutiny
                    # vanished — re-check the same index (now the next
                    # request)
        # admission at decode-step granularity, into the pages left
        # over (its growth page is secured by _admit's ensure; a
        # chunk-admitted prompt counts against max_batch from its
        # FIRST chunk — the engine's concurrency bound covers work in
        # flight, not just work decoding).  A prefill queues on the
        # device behind the run in flight
        with observability.span("serve/admission"):
            while len(self.running) + len(self.prefilling) \
                    < self.max_batch:
                req = self.scheduler.next_admission(arrived_by=clock())
                if req is None:
                    break
                try:
                    self._admit(req, clock)
                    stats["admitted"] += 1
                except (PagePoolExhaustedError, _AdmitDeferred):
                    # pool full (or the scratch slice is busy): wait
                    # (admission never preempts running work — only
                    # decode growth does)
                    self.scheduler.requeue_front(req, preempted=False)
                    break
        # the chunk pass: long prompts stream in, budgeted, BETWEEN
        # the admission pass and the decode dispatch — decode keeps
        # running every step, which is the whole p99 story
        if self.prefilling:
            stats["chunk_tokens"] = self._advance_chunks(clock)
        n = len(self.running)
        stats["evicted"] = self.evictions - evicted_before
        stats["running"] = n
        stats["occupancy"] = (self.allocator.used_pages
                              / self.allocator.num_pages)
        stats["capacity_x"] = self.capacity_multiplier()
        if observability.ring_enabled():
            self._obs_queue_depths()
        if self.spec_k and n:
            return self._spec_step(n, clock, stats)
        # the decode pass, two moves: dispatch the next run if one may
        # go, then land the oldest run not yet landed.  The synchronous
        # order is the case where nothing was in flight and nothing may
        # go ahead: the run dispatched is the run landed.  A prompt
        # mid-chunk interleaves with single decode steps: no run goes
        # ahead of one in flight while it streams in
        ahead = self._may_run_ahead() and not self.prefilling
        lanes = self._next_lanes()
        if landed is None and self._flight is None and ahead and any(
                len(req.tokens) + 2 <= req.max_new_tokens for req in lanes):
            # the engine was empty: the run this call lands goes first,
            # and the one behind it needs a position more than the
            # capacity pass (or the admission) secured
            self._dispatch_decode(lanes)
            lanes = self._next_lanes()
            ahead = self._try_secure_all(lanes)
        if landed is not None:
            # the capacity pass landed this call's run: the next call
            # lands the one dispatched here
            if ahead and lanes:
                self._dispatch_decode(lanes)
        elif self._flight is not None:
            landed = self._decode_window(lanes if ahead else (), clock)
        elif lanes:
            landed = self._decode_window(lanes, clock)
        stats["decoded"] = len(landed or ())
        return stats

    def _decode_window(self, lanes, clock):
        """One ``serve/decode_window``: build and dispatch the decode
        run of ``lanes`` (it becomes the run in flight, ``_ctx`` moves
        on; none where ``lanes`` is empty), then, inside the same span,
        fetch the tokens of the oldest run not yet landed: the run that
        was in flight, dispatched a call ago, which executed while this
        one was built; or, where none was, the run dispatched here (the
        synchronous order).  Returns the lanes landed.  (A run
        dispatched with nothing to land, the first after the engine was
        empty, has its build and dispatch spans and no window: every
        window is a run landed.)

        The span describes the run it LANDS: its host-known tags
        (``batch``, ``bucket``, ``step``, ``ctx_tokens``,
        ``window_tokens``, ``state_lanes``, and ``ahead``: 1 where that
        run was dispatched behind one in flight), made when it was
        dispatched, and what the model counted in it (``extras``), so a
        span's counts are one run's.  The program run that starts
        inside the span is the one it DISPATCHES, a run later where one
        was in flight (it starts when the run being fetched ends): a
        reader that pairs the two sees the same lanes a token apart,
        and a window's sums move by one boundary term."""
        prev = self._flight
        tags = None
        if observability.enabled():
            tags = self._run_tags(len(lanes), [r._ctx for r in lanes],
                                  self.decode_steps, False) \
                if prev is None else \
                self._run_tags(len(prev.lanes), prev.pos, prev.step,
                               prev.ahead)
        with observability.span("serve/decode_window",
                                tags=tags) as window:
            if lanes:
                self._dispatch_decode(lanes)
            landing = prev or self._flight
            with observability.span("serve/decode_fetch"):
                tokens = np.asarray(landing.nxt)   # device->host sync;
                # what the model counted comes in the same fetch, while
                # a span records
                self._set_model_stats(window, landing.extras)
        return self._record(landing, tokens, clock)

    def _run_tags(self, n, pos, step, ahead):
        """What the host knows of a decode run of ``n`` lanes at
        positions ``pos[:n]``, for the span that lands it."""
        # what a sound step reads of each kind of cache: the whole
        # context in the full group (tokens), a window's worth of it in
        # a window group, one state a lane in a state group
        a = self.allocator
        ctx = [int(p) + 1 for p in pos[:n]]
        tags = {"batch": n,
                "bucket": _bucket(n, self.batch_buckets, "batch"),
                "step": step, "ahead": int(ahead),
                "ctx_tokens": sum(ctx)}
        if a.windows:
            tags["window_tokens"] = sum(
                min(c, w.window) for w in a.windows for c in ctx)
        if a.states:
            tags["state_lanes"] = n
        return tags

    def _dispatch_decode(self, lanes):
        """Build and dispatch the decode run of ``lanes`` behind the
        run in flight, if any: it becomes the run in flight and every
        lane's ``_ctx`` moves on."""
        prev = self._flight
        Bb = _bucket(len(lanes), self.batch_buckets, "batch")
        with observability.span("serve/decode_build"):
            pos = np.full(Bb, -1, dtype=np.int32)
            bts = self._zero_bt(Bb)
            # where each lane's token comes from, as the gather takes
            # it: ``>= 0`` the lane's row in the run in flight (counted
            # over the buckets laid end to end), ``-1 - token`` a token
            # the host knows (a lane admitted since, every lane where
            # nothing is in flight; an idle lane: token 0)
            rows = prev.rows if prev is not None else {}
            at = self.batch_buckets.index
            base = sum(self.batch_buckets[:at(prev.nxt.shape[0])]) \
                if prev is not None else 0
            sel = np.full(self.batch_buckets[-1], -1, dtype=np.int32)
            for j, req in enumerate(lanes):
                row = rows.get(id(req))
                sel[j] = -1 - req.tokens[-1] if row is None else base + row
                pos[j] = req._ctx
                bts[..., j, :] = self._bt_row(req.request_id)
            if prev is None:
                toks = self._host_toks(-1 - sel[:Bb])
            elif prev.lanes == lanes:
                # every lane keeps its row: ``nxt`` as it stands (an
                # idle lane's token is never read), and no program
                # between the two runs
                toks = prev.nxt
            else:
                toks = None
                sel = jnp.asarray(sel)
            operands = (jnp.asarray(pos), jnp.asarray(bts))
        with observability.span("serve/decode_dispatch"):
            if toks is None:
                prevs = list(self._idle_prevs)
                prevs[at(prev.nxt.shape[0])] = prev.nxt
                toks = self._next_tokens_fn(tuple(prevs), sel)[at(Bb)]
            _logits, nxt, *extras = self._run(
                self._decode_fn, self.kv, self.state, toks, *operands)
        for req in lanes:
            req._ctx += 1       # written, or in flight to be
        self._flight = _Flight(nxt, extras, lanes, pos, self.decode_steps,
                               prev is not None)
        self.decode_steps += 1
        self.decode_steps_ahead += prev is not None
        if self._idle_prevs is None and self._may_run_ahead():
            self._warm_next_tokens(nxt)

    def _host_toks(self, toks):
        """A decode run's tokens from the host, placed as a decode run
        leaves its own (``nxt``): whichever of the two a run takes, it
        is the same executable."""
        if self._toks_placement is None:
            return jnp.asarray(toks)
        return jax.device_put(toks, self._toks_placement)

    def _land(self, clock):
        """Fetch and record the run in flight with nothing dispatched
        behind it (the batch is ending, a prompt is mid-chunk, or the
        pool ran dry).  Returns the lanes landed."""
        return self._decode_window((), clock)

    def _record(self, flight, tokens, clock):
        """Record and stamp the tokens of ``flight``, retire what
        finished.  A lane that finished a run ago (by ``eos_id``, found
        when that run landed) rode this run spare: its token is dropped.
        Its pages went back to the pool when it retired, which the
        stream orders: whatever takes them next was dispatched after
        this run."""
        if self._flight is flight:
            self._flight = None
        with observability.span("serve/record"):
            t_tok = clock()
            for j, req in enumerate(flight.lanes):
                if req.finish_time is not None:
                    continue
                self._record_token(req, tokens[j], t_tok)
                if self._finished(req):
                    self._retire(req, t_tok)
        f = self._flight
        if f is not None and all(req.finish_time is not None
                                 for req in f.lanes):
            self._flight = None     # every lane ahead rode spare
        return flight.lanes

    def _warm_next_tokens(self, nxt):
        """Compile the gather between two decode runs, once: its idle
        operands, one vector a batch bucket, are placed as a decode run
        leaves ``nxt``, so lanes that join and leave never compile in a
        window."""
        if self._idle_prevs is not None:
            return
        # placed from host zeros: ``jnp.zeros`` would compile a
        # broadcast a bucket, and the gather is to be the ONE program
        # more than the synchronous order compiles
        self._idle_prevs = tuple(
            jax.device_put(np.zeros(Bb, np.int32), nxt.sharding)
            if nxt.committed else jnp.asarray(np.zeros(Bb, np.int32))
            for Bb in self.batch_buckets)
        self._next_tokens_fn(
            self._idle_prevs,
            jnp.asarray(np.zeros(self.batch_buckets[-1], np.int32)))

    def _spec_step(self, n, clock, stats):
        """The speculative decode window: draft K tokens per lane,
        verify all K+1 positions in ONE target dispatch, accept the
        longest matching prefix.  The verify row ``g[j]`` IS the token
        vanilla decode would emit at position ``start + j`` given the
        preceding accepts — so emitting ``g[0..a]`` (a = accepted draft
        count) is bit-identical to running a+1 vanilla steps, and the
        a+1-th token comes free (the classic speculative bonus).
        Rejected span positions hold garbage KV above the new counter:
        never read (ctx_len masks them) and overwritten by the next
        step's drop-fenced writes — rollback is the counter rewind
        itself."""
        K1 = self.spec_k + 1
        nv = np.zeros(n, dtype=np.int32)
        for j, req in enumerate(self.running):
            nv[j] = self._spec_nv(req)
            # lanes admitted THIS step were not in the capacity pass
            # (it runs before admission): secure their span pages now,
            # DEGRADING the window instead of evicting when the pool is
            # dry — admission's own L+1 ensure guarantees nv >= 1, so
            # the step never stalls, it just speculates less
            try:
                self.allocator.ensure(req.request_id,
                                      req._ctx + int(nv[j]))
            except PagePoolExhaustedError:
                nv[j] = min(int(nv[j]),
                            self.allocator.capacity(req.request_id)
                            - req._ctx)
        drafts = self._propose_drafts(nv)
        with observability.span(
                "serve/spec_window",
                tags={"batch": n, "step": self.decode_steps}
                if observability.enabled() else None):
            Bb = _bucket(n, self.batch_buckets, "batch")
            toks = np.zeros((Bb, K1), dtype=np.int32)
            start = np.full(Bb, -1, dtype=np.int32)
            nvb = np.zeros(Bb, dtype=np.int32)
            bts = np.zeros((Bb, self.n_block_entries), dtype=np.int32)
            for j, req in enumerate(self.running):
                toks[j, 0] = req.tokens[-1]
                toks[j, 1:] = drafts[j]
                start[j] = req._ctx
                nvb[j] = nv[j]
                bts[j] = self._bt_row(req.request_id)
            _logits, g = self._run(
                self._spec_verify_fn, self.kv, self.state,
                jnp.asarray(toks), jnp.asarray(start), jnp.asarray(nvb),
                jnp.asarray(bts))
            with observability.span("serve/decode_fetch"):
                g = np.asarray(g)       # device->host sync
            self.decode_steps += 1  # ONE dispatch for up to K+1 tokens
            self.spec_steps += 1
            self.spec_lane_steps += n
        t_tok = clock()
        emitted_total = 0
        for j, req in enumerate(list(self.running)):
            nvj = int(nv[j])
            a = 0
            while a < nvj - 1 and int(toks[j, a + 1]) == int(g[j, a]):
                a += 1
            self.spec_proposed += nvj - 1
            self.spec_accepted += a
            for i in range(a + 1):
                req._ctx += 1
                self._record_token(req, int(g[j, i]), t_tok)
                emitted_total += 1
                self.spec_emitted += 1
                if self._finished(req):
                    break   # eos inside the accepted run: stop HERE
            if self.draft_model is not None:
                # rewind: draft KV above the accepted frontier is
                # garbage; at full accept this leaves gap 1 (the bonus
                # token's position), closed by next step's catch-up
                req._draft_ctx = min(req._draft_ctx, req._ctx)
            if self._finished(req):
                self._retire(req, t_tok)
        stats["decoded"] = n
        stats["spec_emitted"] = emitted_total
        return stats

    def drain(self, max_steps=10000, now=None):
        """Run steps until queues and the running batch are empty (test
        and bench convenience).  Returns the number of steps taken."""
        steps = 0
        while (self.running or self.prefilling
               or self.scheduler.pending()) and steps < max_steps:
            self.step(now=now)
            steps += 1
        return steps
