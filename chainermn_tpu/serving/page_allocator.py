"""Refcounted block allocator + prefix-hash trie for the paged KV cache.

Host-side bookkeeping ONLY: pages are integer ids into the preallocated
device pools (``serving.kv_cache``); no tensor ever passes through this
module, so the decode hot path never copies KV bytes host-side — the
allocator hands out page ids and the device programs scatter/gather
through them.

Round 14 grows the PR 9 allocator into a copy-on-write prefix-sharing
allocator (ISSUE 13): chat-shaped traffic re-sends the same system
prompt / few-shot header thousands of times, and the block table already
indirects every token, so identical prompt prefixes can point at the
SAME physical pages.  Three new pieces:

* **refcounts** — a page may be owned by several sequences at once;
  ``free()`` decrements and only returns pages that hit zero (in table
  order, preserving the FIFO recycle contract at the moment of release);
* **a prefix-hash trie** — live sequences register their prompt's
  page-granular chunks (full ``page_size``-token chunks hash to trie
  nodes bound to the holder's pages; a trailing partial chunk registers
  its token tuple); ``match_prefix`` walks a new prompt down the trie
  and returns the longest shareable page chain.  The match is CONTENT-
  addressed: two prompts reach the same node only via identical token
  prefixes at identical absolute positions, so any holder's page carries
  bit-identical K/V for that span (causal attention + absolute position
  embeddings make K/V at position ``p`` a pure function of tokens
  ``[0..p]``);
* **fork-on-write** — a borrower that must write into a still-shared
  page (its suffix starts mid-page) calls ``fork``: the table entry is
  swapped for a fresh page (refcount moves), and the ENGINE copies the
  page in-graph through the existing scatter path.  The original
  provider never forks: its writes land at slots at or past its own
  frontier, which every borrower's valid region (its matched token
  count) stops strictly short of.

A model whose cache has GROUPS of layers (ISSUE 31: full-attention
layers beside window layers) gets a further pool a window group
(``windows=``), with a block table a sequence of its own:

* a sequence's table is position-major in every group, and ``ensure``
  grows them together; ``slide`` releases the sequence's hold on the
  window pages that lie wholly below its window, so outside a prefill it
  holds ``window / page_size + 2`` pages at most;
* the trie keeps a reference OF ITS OWN on a registered prompt's window
  pages (one page a trie node: any holder's carries the same bytes), for
  as long as the node lives, so that a later prompt can still hit at
  ``m`` tokens after every holder's window has moved on: a hit takes, in
  a window group, the pages covering ``(m - window, m)`` only;
* when a window pool runs short, pages held by the trie alone are given
  up, least recently matched first, before :class:`PagePoolExhaustedError`
  (and with it the eviction of a live sequence) is raised, and a match
  is cut back to the longest ``m`` every group can still serve;
* such a match is cut to whole pages (no fork is needed, so none is
  written for a window group).

A group of layers that keeps one entry a SEQUENCE (ISSUE 33: a
recurrent layer's state; ``states=``) gets SLOTS, not pages:

* a sequence holds one LIVE slot in each such group, taken with its
  first ``ensure`` and given back by ``free``;
* a prefix hit at ``m`` tokens needs the state AS IT WAS AT ``m``, and
  the holder's live state has moved on, so a prefill leaves SNAPSHOTS at
  every ``stride`` tokens (``reserve_snapshots``) and ``register_prefix``
  hands them to the trie node at that depth: the trie's own hold, for as
  long as the node lives;
* ``match_prefix`` cuts a walk back to the deepest node that has a
  snapshot in every state group (and, with window groups, the window's
  pages): whole pages, no fork.  The borrower never writes the snapshot:
  it holds it (``share``) until its prefill has copied it (``restored``);
* snapshots the trie alone holds are given up, least recently matched
  first, before :class:`PagePoolExhaustedError`.

Discipline (mirrors ``_memory_utility.plan_buckets``): every decision is
a pure function of the call sequence — the free list is FIFO over page
ids seeded ``0..P-1``, frees return zero-refcount pages in block-table
order, trie holders are consulted in registration order — so a seeded
request trace produces bit-identical block tables on every run and every
host (the property suite pins this).  Invariants the suite churn-tests:

* ownership: every allocated page is owned by >= 1 sequence and its
  refcount equals the number of tables containing it;
* conservation: ``len(free) + len(distinct owned)`` equals the pool
  size after any alloc/share/fork/free interleaving;
* atomicity: a failed ``ensure``/``fork`` (``PagePoolExhaustedError``)
  leaves the allocator state untouched — OOM is a typed scheduling
  event, never corruption.
"""

from __future__ import annotations

from collections import OrderedDict, deque

from .errors import PagePoolExhaustedError

__all__ = ["BlockAllocator"]


def _common_prefix_len(a, b):
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


class _TrieNode:
    """One page-granular chunk of registered prompt content.

    ``holders`` maps live seq_id -> the page carrying this chunk's K/V
    (insertion order == registration order; matching reads the FIRST
    holder, so the choice is deterministic).  ``partials`` maps live
    seq_id -> (token tuple, page) for a trailing partial chunk hanging
    off this node.
    """

    __slots__ = ("children", "holders", "partials", "window_pages",
                 "snapshots")

    def __init__(self, n_windows=0, n_states=0):
        self.children = {}
        self.holders = OrderedDict()
        self.partials = OrderedDict()
        # the page the trie itself holds of this chunk in each window
        # group (None: never written by a holder, or given up)
        self.window_pages = [None] * n_windows
        # the slot that holds the state as it stood at this chunk's END,
        # in each state group (None: not on a stride, or given up)
        self.snapshots = [None] * n_states

    @property
    def dead(self):
        return not (self.children or self.holders or self.partials)


class PrefixMatch(list):
    """What ``match_prefix`` found: the full group's shareable pages, as
    the list it always was, and in ``windows`` each window group's table
    prefix for the borrower (``(table, low)``: entries below ``low`` lie
    under the window and are not held)."""

    windows = ()
    snapshots = ()      # each state group's slot to start from


class _Retained:
    """What the trie holds of a pool, least recently matched first, and
    the giving up of it: ``refs`` counts a sequence's hold and the
    trie's alike, ``retained`` maps an id the trie holds to ``(trie
    node, group index)``, ``_forget`` clears the node's name for it."""

    def release(self, item):
        self.refs[item] -= 1
        if self.refs[item] == 0:
            del self.refs[item]
            self.free.append(item)

    def make_room(self, need):
        """Whether ``need`` are free, after giving up as many that the
        trie alone holds as it takes, least recently matched first
        (none, where even all of them would not do)."""
        short = need - len(self.free)
        if short <= 0:
            return True
        alone = [item for item in self.retained if self.refs[item] == 1]
        if len(alone) < short:
            return False
        for item in alone[:short]:
            self._forget(*self.retained.pop(item))
            self.release(item)
        return True

    @property
    def retained_alone(self):
        """How many the trie holds and no sequence does."""
        return sum(1 for item in self.retained if self.refs[item] == 1)


class _StatePool(_Retained):
    """One state group's slots: each sequence's live slot, the snapshots
    a prefill in flight is writing (``pending``: by position, until the
    prompt is registered), the snapshot a borrower is about to copy
    (``restoring``), and the snapshots the trie holds."""

    def __init__(self, num_slots, stride, page_size):
        if num_slots <= 0 or stride <= 0 or stride % page_size:
            raise ValueError(
                f"a state group needs slots and a snapshot stride that is "
                f"a multiple of the page size; got {num_slots} slots, "
                f"stride {stride}, page size {page_size}")
        self.num_slots, self.stride = int(num_slots), int(stride)
        self.free = deque(range(self.num_slots))
        self.refs = {}
        self.live = {}               # seq_id -> slot
        self.pending = {}            # seq_id -> {position: slot}
        self.restoring = {}          # seq_id -> snapshot slot
        self.retained = OrderedDict()   # slot -> (trie node, group index)

    @staticmethod
    def _forget(node, g):
        node.snapshots[g] = None

    def take(self):
        slot = self.free.popleft()
        self.refs[slot] = 1
        return slot

    def drop(self, seq_id):
        """Everything ``seq_id`` holds here but its live slot."""
        for slot in self.pending.pop(seq_id, {}).values():
            self.release(slot)
        if seq_id in self.restoring:
            self.release(self.restoring.pop(seq_id))


class _WindowPool(_Retained):
    """One window group's pages: refcounts (a sequence's table and the
    trie each count one), the sequences' tables with how far each has
    slid, and the pages the trie holds, least recently matched first."""

    def __init__(self, num_pages, window, page_size):
        if num_pages <= 0 or window <= 0 or window % page_size:
            raise ValueError(
                f"a window group needs pages and a window that is a "
                f"multiple of the page size; got {num_pages} pages, "
                f"window {window}, page size {page_size}")
        self.num_pages, self.window = int(num_pages), int(window)
        self.free = deque(range(self.num_pages))
        self.refs = {}
        self.tables = {}             # seq_id -> [page ids], position-major
        self.low = {}                # seq_id -> first entry still held
        self.retained = OrderedDict()   # page -> (trie node, group index)

    @staticmethod
    def _forget(node, g):
        node.window_pages[g] = None


class BlockAllocator:
    """Fixed pool of ``num_pages`` pages, ``page_size`` token slots each.

    ``ensure(seq_id, n_tokens)`` grows sequence ``seq_id``'s block table
    to cover ``n_tokens`` positions (idempotent; allocation only ever
    appends — positions are immutable once written).  ``share`` seeds a
    NEW sequence's table with another sequence's pages (refcount++),
    ``fork`` swaps a still-shared table entry for a fresh page
    (copy-on-write), and ``free(seq_id)`` decrements every owned page's
    refcount, returning only zero-refcount pages to the free list in
    table order.
    """

    def __init__(self, num_pages, page_size, windows=(), states=()):
        """``windows``: ``(num_pages, window)`` of each further group of
        layers that keeps the last ``window`` positions only;
        ``states``: ``(num_slots, stride)`` of each group that keeps one
        entry a sequence, with a snapshot every ``stride`` tokens."""
        if num_pages <= 0 or page_size <= 0:
            raise ValueError("num_pages and page_size must be positive")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.windows = [_WindowPool(p, w, self.page_size)
                        for p, w in windows]
        self.states = [_StatePool(n, stride, self.page_size)
                       for n, stride in states]
        self._free = deque(range(self.num_pages))
        # OrderedDict: iteration order == admission order (the scheduler's
        # eviction policy reads it newest-first)
        self._tables = OrderedDict()
        self._refs = {}          # page id -> number of tables holding it
        self._trie = self._node()
        self._trie_refs = {}     # seq_id -> [(parent, key, node), ...]

    def _node(self):
        return _TrieNode(len(self.windows), len(self.states))

    # -- queries -------------------------------------------------------------

    @property
    def free_pages(self):
        return len(self._free)

    @property
    def used_pages(self):
        """DISTINCT pages owned by at least one sequence."""
        return self.num_pages - len(self._free)

    def pages_for(self, n_tokens):
        """Pages needed to hold ``n_tokens`` positions."""
        return -(-max(0, int(n_tokens)) // self.page_size)

    def sequences(self):
        """Sequence ids in admission order (oldest first)."""
        return list(self._tables)

    def block_table(self, seq_id):
        """The sequence's page ids, position-major (a copy)."""
        return list(self._tables[seq_id])

    def window_table(self, seq_id, group=0):
        """``(table, low)`` of the sequence in a window group: its page
        ids, position-major (NOT a copy), of which the entries below
        ``low`` have been released and may name anyone's page."""
        w = self.windows[group]
        return w.tables[seq_id], w.low[seq_id]

    def state_slots(self, seq_id, group=0):
        """``(live, source)`` of the sequence in a state group: its own
        slot, and the slot its next prefill starts from: the snapshot a
        hit gave it, until ``restored``, then its own."""
        st = self.states[group]
        live = st.live[seq_id]
        return live, st.restoring.get(seq_id, live)

    def capacity(self, seq_id):
        """Token positions the sequence's current pages can hold."""
        return len(self._tables[seq_id]) * self.page_size

    def refcount(self, page):
        """How many tables hold ``page`` (0 = free)."""
        return self._refs.get(page, 0)

    def unique_pages(self, seq_id):
        """Pages ONLY this sequence owns — what evicting it would
        actually return to the pool (the eviction-livelock guard's
        accounting; shared pages stay alive through their other
        holders)."""
        return sum(1 for p in self._tables[seq_id]
                   if self._refs[p] == 1)

    def logical_pages(self):
        """Sum of table lengths, counting shared pages once PER HOLDER —
        the pages an unshared pool would need for the same residency.
        ``logical_pages() / used_pages`` is the effective-capacity
        multiplier prefix sharing buys (the bench row reports it)."""
        return sum(len(t) for t in self._tables.values())

    # -- mutation ------------------------------------------------------------

    def ensure(self, seq_id, n_tokens):
        """Grow ``seq_id``'s table to cover ``n_tokens`` positions.

        Registers the sequence on first call.  Atomic: raises
        :class:`PagePoolExhaustedError` (state unchanged) when the free
        list cannot cover the growth.  Returns the block table (copy).
        """
        table = self._tables.get(seq_id)
        if table is None:
            table = []
        need = self.pages_for(n_tokens) - len(table)
        if need > len(self._free):
            raise PagePoolExhaustedError(need, len(self._free),
                                         self.num_pages)
        for w in self.windows:
            w_need = self.pages_for(n_tokens) - len(w.tables.get(seq_id, ()))
            if not w.make_room(w_need):
                raise PagePoolExhaustedError(w_need, len(w.free),
                                             w.num_pages)
        for st in self.states:
            if seq_id not in st.live and not st.make_room(1):
                raise PagePoolExhaustedError(1, 0, st.num_slots)
        if seq_id not in self._tables:
            self._tables[seq_id] = table
        for _ in range(max(0, need)):
            p = self._free.popleft()
            self._refs[p] = 1
            table.append(p)
        for w in self.windows:
            w_table = w.tables.setdefault(seq_id, [])
            w.low.setdefault(seq_id, 0)
            while len(w_table) < len(table):
                p = w.free.popleft()
                w.refs[p] = 1
                w_table.append(p)
        for st in self.states:
            if seq_id not in st.live:
                st.live[seq_id] = st.take()
        return list(table)

    def reserve_snapshots(self, seq_id, positions):
        """A prefill of ``seq_id`` is about to pass ``positions``: take
        a slot in every state group for each of them that lies on the
        group's stride (and has none yet), for the program to write the
        state into as it stands there.  The sequence holds them until
        ``register_prefix`` hands them to the trie.  Atomic: raises
        :class:`PagePoolExhaustedError` (state unchanged) where a group
        cannot give them even after the trie's alone are given up.
        Returns, a group, ``{position: slot}`` of all it holds."""
        wanted = []
        for st in self.states:
            held = st.pending.get(seq_id, {})
            new = sorted({p for p in positions
                          if p > 0 and p % st.stride == 0 and p not in held})
            if not st.make_room(len(new)):
                raise PagePoolExhaustedError(len(new), len(st.free),
                                             st.num_slots)
            wanted.append(new)
        for st, new in zip(self.states, wanted):
            held = st.pending.setdefault(seq_id, {})
            for p in new:
                held[p] = st.take()
        return [dict(st.pending.get(seq_id, {})) for st in self.states]

    def restored(self, seq_id):
        """The sequence's prefill has copied the snapshot a hit gave it:
        its hold on it ends, and what it starts from next is its own
        slot."""
        for st in self.states:
            if seq_id in st.restoring:
                st.release(st.restoring.pop(seq_id))

    def slide(self, seq_id, position):
        """The sequence's next query sits at ``position``: release its
        hold on the window pages that lie wholly below ``position -
        window + 1`` (what the trie holds of them stays).  Nothing to do
        for an allocator without window groups."""
        for w in self.windows:
            table = w.tables[seq_id]
            upto = min((position - w.window + 1) // self.page_size,
                       len(table))
            for i in range(w.low[seq_id], upto):
                w.release(table[i])
            w.low[seq_id] = max(w.low[seq_id], upto)

    def share(self, seq_id, pages):
        """Seed a NEW sequence's table with shared pages (refcount++ on
        each; the pages must be live).  Must precede any ``ensure`` for
        ``seq_id`` — sharing seeds a prefix, it never splices."""
        if seq_id in self._tables:
            raise ValueError(f"share() must seed a new sequence; "
                             f"{seq_id!r} already has a table")
        for p in pages:
            if self._refs.get(p, 0) < 1:
                raise ValueError(f"cannot share non-live page {p}")
        for p in pages:
            self._refs[p] += 1
        self._tables[seq_id] = list(pages)
        for w, (w_table, low) in zip(self.windows,
                                     getattr(pages, "windows", ())):
            for p in w_table[low:]:
                w.refs[p] += 1
            w.tables[seq_id], w.low[seq_id] = list(w_table), low
        for st, slot in zip(self.states, getattr(pages, "snapshots", ())):
            st.refs[slot] += 1
            st.restoring[seq_id] = slot

    def fork(self, seq_id, index):
        """Copy-on-write: swap the (shared) page at ``index`` of
        ``seq_id``'s table for a fresh page.  Returns ``(old, new)`` —
        the CALLER copies the device bytes ``old -> new`` in-graph.
        No-op ``(old, old)`` when the page is no longer shared (the
        other holders freed between share and write).  Atomic: raises
        :class:`PagePoolExhaustedError` (state unchanged) when the pool
        is dry."""
        table = self._tables[seq_id]
        old = table[index]
        if self._refs[old] <= 1:
            return old, old
        if not self._free:
            raise PagePoolExhaustedError(1, 0, self.num_pages)
        new = self._free.popleft()
        self._refs[old] -= 1
        self._refs[new] = 1
        table[index] = new
        return old, new

    def free(self, seq_id):
        """Release every page of ``seq_id`` (eviction and completion
        share this path): refcount-- each; pages hitting ZERO rejoin the
        free list in table order (shared pages stay alive through their
        other holders).  Unregisters the sequence's trie entries.
        Returns the number of pages actually returned to the pool."""
        table = self._tables.pop(seq_id)
        for w in self.windows:
            low = w.low.pop(seq_id)
            for p in w.tables.pop(seq_id)[low:]:
                w.release(p)
        for st in self.states:
            st.drop(seq_id)
            if seq_id in st.live:
                st.release(st.live.pop(seq_id))
        self.unregister_prefix(seq_id)
        freed = 0
        for p in table:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)
                freed += 1
        return freed

    # -- the prefix-hash trie ------------------------------------------------

    def register_prefix(self, seq_id, tokens):
        """Publish ``seq_id``'s prompt as shareable: each full
        ``page_size``-token chunk binds a trie node to the sequence's
        page at that index; a trailing partial chunk registers its token
        tuple (borrowers of a partial page fork before writing).  The
        table must already cover the prompt.  Idempotent per sequence
        (re-registration replaces)."""
        if seq_id in self._trie_refs:
            self.unregister_prefix(seq_id)
        tokens = tuple(tokens)
        table = self._tables[seq_id]
        S = self.page_size
        n_full = len(tokens) // S
        refs = []
        node = self._trie
        for i in range(n_full):
            chunk = tokens[i * S:(i + 1) * S]
            child = node.children.get(chunk)
            if child is None:
                child = node.children[chunk] = self._node()
            child.holders[seq_id] = table[i]
            for g, w in enumerate(self.windows):
                # the trie's own hold on the chunk's window page, taken
                # from the first holder that still has one
                if child.window_pages[g] is None and i >= w.low[seq_id]:
                    page = w.tables[seq_id][i]
                    child.window_pages[g] = page
                    w.refs[page] += 1
                    w.retained[page] = (child, g)
            for g, st in enumerate(self.states):
                # the snapshot this sequence's prefill left at the
                # chunk's end becomes the trie's (the sequence's hold
                # moves; a node that has one already keeps it)
                slot = st.pending.get(seq_id, {}).get((i + 1) * S)
                if slot is not None and child.snapshots[g] is None:
                    del st.pending[seq_id][(i + 1) * S]
                    child.snapshots[g] = slot
                    st.retained[slot] = (child, g)
            refs.append((node, chunk, child))
            node = child
        rem = tokens[n_full * S:]
        # a window or a state group shares whole pages
        if rem and not (self.windows or self.states):
            node.partials[seq_id] = (rem, table[n_full])
            refs.append((None, None, node))   # partial ref marker
        self._trie_refs[seq_id] = refs
        for st in self.states:      # the prompt is written: what no node
            st.drop(seq_id)         # took is given back

    def unregister_prefix(self, seq_id):
        """Remove ``seq_id``'s trie entries, pruning nodes that die
        (deepest first, so a long-running server's trie stays bounded by
        LIVE prompt content)."""
        refs = self._trie_refs.pop(seq_id, None)
        if not refs:
            return
        for parent, key, node in reversed(refs):
            if parent is None:               # partial ref marker
                node.partials.pop(seq_id, None)
            else:
                node.holders.pop(seq_id, None)
                if node.dead:
                    parent.children.pop(key, None)
                    for w, page in zip(self.windows, node.window_pages):
                        if page is not None:
                            del w.retained[page]
                            w.release(page)
                    for st, slot in zip(self.states, node.snapshots):
                        if slot is not None:
                            del st.retained[slot]
                            st.release(slot)

    def match_prefix(self, tokens, cap):
        """Longest shareable prefix of ``tokens`` against live
        registrations, capped at ``cap`` tokens (the engine passes
        ``len(prompt) - 1`` so prefill always keeps >= 1 suffix token to
        produce the first-generation logits).

        Returns ``(pages, matched, n_full, partial)``: the shareable
        page chain, total matched token count, how many of those pages
        are FULL (immutable — safe to share forever), and how many
        tokens of a trailing PARTIAL page matched (> 0 means the caller
        must fork that last page before its first write into it).
        Deterministic: full chunks take the first-registered holder's
        page; the partial winner is the first registration achieving the
        longest common prefix.
        """
        tokens = tuple(tokens)
        cap = min(int(cap), len(tokens))
        S = self.page_size
        pages, path = [], []
        node = self._trie
        n_full = 0
        while (n_full + 1) * S <= cap:
            chunk = tokens[n_full * S:(n_full + 1) * S]
            child = node.children.get(chunk)
            if child is None or not child.holders:
                break
            pages.append(next(iter(child.holders.values())))
            path.append(child)
            node = child
            n_full += 1
        if self.windows or self.states:
            return self._match_groups(pages, path)
        matched = n_full * S
        best_c, best_page = 0, None
        for ptoks, ppage in node.partials.values():
            c = min(_common_prefix_len(ptoks, tokens[matched:]),
                    cap - matched)
            if c > best_c:
                best_c, best_page = c, ppage
        if best_c:
            pages.append(best_page)
            matched += best_c
        return pages, matched, n_full, best_c

    def _match_groups(self, pages, path):
        """Cut a walk of whole pages (``pages``, through the trie nodes
        ``path``) back to the longest ``m`` that every group can serve:
        the trie still holds, in every window group, the pages covering
        ``(m - window, m)``, and in every state group a snapshot of the
        state at ``m``.  Take those: they become the most recently
        matched."""
        S = self.page_size
        held = [all(p is not None for p in n.window_pages) for n in path]
        run, d = 0, 0
        for i, ok in enumerate(held):       # run: held chunks ending at i
            run = run + 1 if ok else 0
            lows = [max(0, (i + 1) * S - w.window + 1) // S
                    for w in self.windows]
            if run >= i + 1 - min(lows, default=0) \
                    and all(s is not None for s in path[i].snapshots):
                d = i + 1
        out = PrefixMatch(pages[:d])
        out.windows, out.snapshots = [], []
        for g, w in enumerate(self.windows):
            low = max(0, d * S - w.window + 1) // S
            table = [0] * low + [n.window_pages[g] for n in path[low:d]]
            for page in table[low:]:
                w.retained.move_to_end(page)
            out.windows.append((table, low))
        if d:
            for st, slot in zip(self.states, path[d - 1].snapshots):
                st.retained.move_to_end(slot)
                out.snapshots.append(slot)
        return out, d * S, d, 0

    @property
    def window_used_pages(self):
        """Distinct pages of the window groups that a sequence or the
        trie holds (0 without one)."""
        return sum(w.num_pages - len(w.free) for w in self.windows)

    @property
    def window_retained_pages(self):
        """Those of them that the trie alone holds."""
        return sum(w.retained_alone for w in self.windows)

    def group_stats(self):
        """The pools beside the first, by KIND, as ``serve/step``'s
        stats: a kind the allocator has no group of gives none."""
        out = {}
        if self.windows:
            out.update(
                window_used_pages=self.window_used_pages,
                window_num_pages=sum(w.num_pages for w in self.windows),
                window_retained_pages=self.window_retained_pages)
        if self.states:
            out.update(
                state_used_slots=sum(st.num_slots - len(st.free)
                                     for st in self.states),
                state_num_slots=sum(st.num_slots for st in self.states),
                state_retained_slots=sum(st.retained_alone
                                         for st in self.states))
        return out

    # -- invariant check (the property suite's oracle) -----------------------

    def check(self):
        """Assert the ownership/conservation invariants; returns True so
        tests can ``assert alloc.check()`` after every churn step."""
        counts = {}
        for t in self._tables.values():
            for p in t:
                counts[p] = counts.get(p, 0) + 1
        if len(self._free) + len(counts) != self.num_pages:
            raise AssertionError(
                f"page conservation violated: {len(self._free)} free + "
                f"{len(counts)} distinct owned != {self.num_pages}")
        if counts != self._refs:
            raise AssertionError(
                f"refcount drift: tables say {counts}, refs say "
                f"{self._refs}")
        if set(self._free) & set(counts):
            raise AssertionError("page both free and owned")
        all_pages = list(self._free) + list(counts)
        if not all(0 <= p < self.num_pages for p in all_pages):
            raise AssertionError("page id out of range")
        for seq_id, refs in self._trie_refs.items():
            if seq_id not in self._tables:
                raise AssertionError(
                    f"trie registration for dead sequence {seq_id!r}")
        for g, w in enumerate(self.windows):
            counts = {}
            for seq_id, t in w.tables.items():
                if seq_id not in self._tables:
                    raise AssertionError(
                        f"window table for dead sequence {seq_id!r}")
                for p in t[w.low[seq_id]:]:
                    counts[p] = counts.get(p, 0) + 1
            for page, (node, group) in w.retained.items():
                if group != g or node.window_pages[g] != page:
                    raise AssertionError(
                        f"window page {page} retained by a node that "
                        f"does not name it")
                counts[page] = counts.get(page, 0) + 1
            if counts != w.refs:
                raise AssertionError(
                    f"window refcount drift: holders say {counts}, refs "
                    f"say {w.refs}")
            if len(w.free) + len(counts) != w.num_pages \
                    or set(w.free) & set(counts):
                raise AssertionError("window page conservation violated")
        for g, st in enumerate(self.states):
            counts = {}
            for holds in (st.live, st.restoring):
                for seq_id, slot in holds.items():
                    if seq_id not in self._tables:
                        raise AssertionError(
                            f"state slot for dead sequence {seq_id!r}")
                    counts[slot] = counts.get(slot, 0) + 1
            for seq_id, held in st.pending.items():
                if seq_id not in self._tables:
                    raise AssertionError(
                        f"snapshot pending for dead sequence {seq_id!r}")
                for slot in held.values():
                    counts[slot] = counts.get(slot, 0) + 1
            for slot, (node, group) in st.retained.items():
                if group != g or node.snapshots[g] != slot:
                    raise AssertionError(
                        f"slot {slot} retained by a node that does not "
                        f"name it")
                counts[slot] = counts.get(slot, 0) + 1
            if counts != st.refs:
                raise AssertionError(
                    f"slot refcount drift: holders say {counts}, refs say "
                    f"{st.refs}")
            if len(st.free) + len(counts) != st.num_slots \
                    or set(st.free) & set(counts):
                raise AssertionError("state slot conservation violated")
        return True
