"""Paged cache: preallocated device pools + in-graph page writes.

What a token leaves in the cache, per layer, is the MODEL's to declare
(``serve_cache_entry()``): a tuple of per-token shapes, one pool array
``[L, P, S, *shape]`` (layers × pages × page slots × the entry) for each.
A GPT-2-shaped model declares K and V of ``[H · D]`` — two pools, named
``k_pool``/``v_pool`` here, a token's heads side by side in the lanes
(an entry whose minor dimension is whole lane tiles is stored as the
programs compute on it; one of ``[H, D]`` with ``D`` = 64 was converted
whole, 805 MB a pool, four times a step: PR 43); a latent-attention
model declares one vector
(``[kv_rank + rope_dim]``) — one pool.  The pools are allocated ONCE at
engine construction and only ever updated functionally inside the
compiled prefill/decode programs (donated on real accelerators, so XLA
writes pages in place).  Pages are bf16 by default: the decode step is
HBM-bandwidth-bound on cache reads (PR 3's byte roofline applied to
serving), so halving the stored byte per element is the single biggest
lever — the dtype is pinned at construction and every write casts
through it.

Token ``t`` of a sequence lives at ``(page=block_table[t // S],
slot=t % S)``.  The writers below map positions to ``(page, slot)``
pairs in-graph and scatter with ``mode="drop"``: a lane that must not
write (idle decode slot, prompt padding) is routed to the
out-of-range page id ``P`` and dropped by XLA — no host-side masking,
no host-side copies, one scatter per pool per layer.  They address the
two leading axes of a layer's pool only, so they serve any entry shape.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp

from ..observability import role

__all__ = ["PagedKVCache", "PerSequence", "write_prompt_kv",
           "write_prompt_kv_at", "write_token_kv", "write_span_kv",
           "copy_page", "insert_pages"]


@dataclasses.dataclass(frozen=True)
class PerSequence:
    """The span of a cache group that keeps ONE entry a sequence, not an
    entry a token: a recurrent layer's state (where a full group's span
    is ``None`` and a window group's its window).  ``stride``: the
    tokens between the snapshots of it that a prefill leaves for the
    prefix trie, a multiple of the page size."""

    stride: int


def _geometry(pool, layer):
    """``(P, S)`` of a layer's pool, or of the whole ``[L, P, S, ...]``
    pool addressed at ``layer``."""
    lead = 0 if layer is None else 1
    return pool.shape[lead], pool.shape[lead + 1]


def _scatter(pool, layer, pages, slots, kv):
    """The one drop-fenced scatter of every writer.  ``layer=None``:
    ``pool`` is one layer's ``[P, S, ...]``.  With a ``layer``, ``pool``
    is the whole ``[L, P, S, ...]`` array and the entries land in that
    layer of it directly — no layer slice is taken out and put back, so
    a donated pool is updated in place by the scatter alone.  The layer
    is a Python number, or a traced scalar: a model that runs its blocks
    several times keeps a cache layer a pass and a block, and inside its
    device loop over passes that index is known on the device alone
    (``LoopedLM``); the pool is then the loop's carry, and the scatter
    updates the carry in place."""
    at = pool.at[pages, slots] if layer is None \
        else pool.at[layer, pages, slots]
    return at.set(kv.astype(pool.dtype), mode="drop")


@role("cache_write")
def write_prompt_kv(pool_l, kv, block_table_row, true_len, layer=None):
    """Write a whole prompt's entries into one layer's pool.

    ``pool_l``: ``[P, S, *entry]``.  ``kv``: ``[T, *entry]`` (position-major,
    possibly padded past ``true_len``).  ``block_table_row``: ``[N]``
    page ids covering at least ``true_len`` positions.  Positions
    ``>= true_len`` scatter to the out-of-range page and are dropped.
    ``layer``: see :func:`_scatter` (every writer takes it).
    """
    P, S = _geometry(pool_l, layer)
    T = kv.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    pages = jnp.where(t < true_len, block_table_row[t // S], P)
    return _scatter(pool_l, layer, pages, t % S, kv)


@role("cache_write")
def write_prompt_kv_at(pool_l, kv, block_table_row, start, true_len,
                       layer=None):
    """Offset prompt writer for the prefix-sharing suffix prefill.

    ``kv``: ``[T, *entry]`` SUFFIX K/V — position ``t`` of the suffix
    lives at absolute position ``start + t``, so the scatter addresses
    ``block_table_row[(start + t) // S]`` slot ``(start + t) % S``.
    Positions ``>= true_len`` (suffix padding) drop.  ``start = 0``
    degenerates to :func:`write_prompt_kv`.
    """
    P, S = _geometry(pool_l, layer)
    T = kv.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    posn = start + t
    pages = jnp.where(t < true_len, block_table_row[posn // S], P)
    return _scatter(pool_l, layer, pages, posn % S, kv)


def copy_page(*pools_src_dst):
    """``copy_page(*pools, src, dst)`` — fork-on-write: duplicate page
    ``src`` into page ``dst`` across every layer of EVERY pool — the
    copy-on-write half of the round-14 prefix sharing, run in-graph
    through the same scatter machinery as the writers (``mode="drop"``
    fencing intact).  ``src``/``dst`` are TRACED scalars, so one
    compiled program serves every fork (the never-retrace contract
    covers forks).  Returns the pools, as a tuple."""
    *pools, src, dst = pools_src_dst
    return tuple(p.at[:, dst].set(p[:, src], mode="drop") for p in pools)


def insert_pages(pool, block, rows):
    """Disaggregation ship receiver: scatter a transferred page block
    ``[L, nb, S, *entry]`` (the prefill slice's finished pages) into the
    decode pool at page ids ``rows`` (``[nb]`` int32; padding rows carry
    the out-of-range id ``P`` and drop)."""
    return pool.at[:, rows].set(block.astype(pool.dtype), mode="drop")


@role("cache_write")
def write_token_kv(pool_l, kv, block_tables, pos, layer=None):
    """Write one decode token per batch lane into one layer's pool.

    ``kv``: ``[B, *entry]``.  ``pos``: ``[B]`` int32 position being
    written; ``pos < 0`` marks an idle lane (dropped).  ``block_tables``:
    ``[B, N]``.
    """
    P, S = _geometry(pool_l, layer)
    b = jnp.arange(pos.shape[0])
    safe = jnp.maximum(pos, 0)
    pages = jnp.where(pos >= 0, block_tables[b, safe // S], P)
    return _scatter(pool_l, layer, pages, safe % S, kv)


@role("cache_write")
def write_span_kv(pool_l, kv, block_tables, start, n_valid, layer=None):
    """Write a SPAN of speculative tokens per batch lane (round 20).

    ``kv``: ``[B, K1, *entry]`` — token ``j`` of lane ``b`` lands at
    absolute position ``start[b] + j``.  ``start``: ``[B]`` int32;
    ``start < 0`` marks an idle lane (every write dropped).
    ``n_valid``: ``[B]`` int32 — only the first ``n_valid[b]`` span
    slots write (a lane near its emit budget or the context edge
    speculates fewer than K tokens; the surplus scatters to the
    out-of-range page and drops).  This drop-fencing is ALSO the
    rollback story: rejected speculative writes are never un-written —
    the engine just rewinds the lane's position counter, the stale
    slots are masked out of every later read by ``ctx_len``/causality,
    and the next step's writes overwrite them before they are ever
    visible.
    """
    P, S = _geometry(pool_l, layer)
    B, K1 = kv.shape[0], kv.shape[1]
    b = jnp.arange(B, dtype=jnp.int32)[:, None]
    j = jnp.arange(K1, dtype=jnp.int32)[None, :]
    posn = start[:, None] + j
    live = (start[:, None] >= 0) & (j < n_valid[:, None])
    safe = jnp.maximum(posn, 0)
    pages = jnp.where(live, block_tables[b, safe // S], P)
    return _scatter(pool_l, layer, pages, safe % S, kv)


class PagedKVCache:
    """The engine-owned pools.  ``entry`` is what the model declares a
    token leaves in a layer (a tuple of per-token shapes); construction
    allocates one ``[L, P, S, *shape]`` array of zeros for each, in
    ``pools``.  The engine threads them through its jit programs and
    stores back the returned (donated) arrays.

    A model whose layers keep their entries for different spans declares
    its cache by GROUPS of layers, each with a page count of its own:
    ``more`` is the further groups' ``(n_layers, num_pages, entry)``,
    and their arrays follow the first group's in ``pools``.  ``n_layers``,
    ``num_pages``, ``entry`` and ``page_bytes`` stay the first group's;
    ``pool_bytes`` is every group's.

    A group that keeps one entry a SEQUENCE (:class:`PerSequence`) gets
    SLOTS, not pages: ``states`` is such groups' ``(n_layers, num_slots,
    entry)``, each shape of the entry one float32 array ``[L, slots,
    *shape]`` after the pages' in ``pools``."""

    def __init__(self, n_layers, num_pages, page_size, entry,
                 dtype=jnp.bfloat16, more=(), states=()):
        self.n_layers = int(n_layers)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.entry = tuple(tuple(int(d) for d in shape) for shape in entry)
        self.dtype = jnp.dtype(dtype)
        def sized(groups):
            return tuple((int(n), int(count),
                          tuple(tuple(int(d) for d in shape) for shape in e))
                         for n, count, e in groups)
        self.groups = ((self.n_layers, self.num_pages, self.entry),) \
            + sized(more)
        self.state_groups = sized(states)
        self.pools = [
            jnp.zeros((n, pages, self.page_size) + shape, self.dtype)
            for n, pages, e in self.groups for shape in e] + [
            jnp.zeros((n, slots) + shape, jnp.float32)
            for n, slots, e in self.state_groups for shape in e]

    # the names of a two-array (K, V) entry's pools
    @property
    def k_pool(self):
        return self.pools[0]

    @k_pool.setter
    def k_pool(self, value):
        self.pools[0] = value

    @property
    def v_pool(self):
        return self.pools[1]

    @v_pool.setter
    def v_pool(self, value):
        self.pools[1] = value

    @property
    def page_bytes(self):
        """Bytes one page holds in one layer, over every array of the
        declared entry (the roofline accounting in docs/serving.md
        prices decode reads with this)."""
        return (sum(math.prod(shape) for shape in self.entry)
                * self.page_size * self.dtype.itemsize)

    @property
    def pool_bytes(self):
        return sum(n * pages * sum(math.prod(shape) for shape in e)
                   for n, pages, e in self.groups) \
            * self.page_size * self.dtype.itemsize \
            + sum(n * slots * sum(math.prod(shape) for shape in e) * 4
                  for n, slots, e in self.state_groups)
