"""Host-side allocator for the paged KV-cache pool (the vLLM block manager
analog, sized for the GenerationEngine's fixed-shape decode step) plus the
prefix cache that shares immutable full pages across requests.

The device side is dumb on purpose: per layer, one persistable
``[n_pages * page_size, feat]`` pool tensor that the compiled programs
gather/scatter through block tables (ops/generation_ops.py). All policy
lives here, on the host, where it costs nothing per token:

- **page free-list + refcounts** — page 0 is a reserved *scratch* page that
  is never handed out. Idle decode slots and padded prefill tail positions
  write there (their block-table entries are 0), so a fixed-shape program
  can always run all slots without conditionals; scratch contents are
  garbage by design and masked out of every attention read. Every live page
  carries a refcount: 1 for a private page, +1 per extra slot sharing it,
  +1 while the prefix cache holds it. A page returns to the free list only
  at refcount 0.
- **slot free-list** — a slot is one decode lane in the fixed [max_slots]
  step. Admission takes a slot + enough pages for the request's worst case
  (prompt + max_new tokens, the reservation-at-admit policy: admission can
  never deadlock mid-decode needing a page that isn't there). Shared prefix
  pages satisfy the leading part of the reservation without consuming free
  pages.
- **page reuse on retirement** — release() drops one reference per table
  entry; pages nobody else holds return to their free list and the next
  admission reuses them without touching the device (stale rows are
  overwritten by prefill/decode writes before any read, see
  docs/serving.md lifecycle).

**PrefixCache** is a prompt-token trie over *full* pages: a node at depth
k stands for the exact first ``k * page_size`` prompt tokens and its key is
(its parent node's id, the page's own ``page_size`` tokens) — token tuples
and an exact parent, not hashes, so no collisions, and a lookup or an insert
touches each prompt token once (keyed by the whole prefix, an 8k-token
prompt of 64-token pages hashed half a million tokens a lookup: a quarter
of the scheduler's wall in a cell of long shared documents, PERF.md PR 31).
The value is the pool page holding those positions' K/V. Shared pages are immutable by construction — a prefill after a prefix
hit starts at the first uncached position, and decode writes land at
positions >= the prompt length, so no program ever writes through a shared
table entry; copy-on-write is unnecessary. Lookup always leaves at least
the final prompt token uncached (its hidden state must be computed to
produce the first sampled logits). Eviction is LRU over unreferenced
entries (descendants first, so the trie never has unreachable tails) and
runs on demand when admission wants pages the free list can't supply.

**A fixed per-sequence state beside the pages** (a model whose layers are
not all attention: a short convolution's last rows, a recurrence's carry).
The engine keeps it in per-slot state rows (row 0 scratch, slot s at row
s + 1) that no allocator manages: a slot owns its row. What the prefix cache
has to add is that *a hit must restore the state*: pages alone put the
attention layers at the boundary, not the others. So with ``state_bytes`` >
0 a trie node may carry a **snapshot** of the state at its boundary (an
opaque handle the engine made: device arrays, ``state_bytes`` each),
``lookup_state`` returns pages only down to the deepest boundary that has
one, with it, and reports how deep the prompt *matched* — where a later
prompt's prefill should leave a snapshot so that the next one can hit.
Snapshots are budgeted in bytes, whatever a state is wide (a few KB of a
convolution's rows, tens of MB of a recurrence's): they are held under the
reserve ``snapshot_bytes`` (the coldest go first, LRU among the nodes that
have one; the node and its page stay) and go with their node on eviction;
``stats()`` reports the bytes held and reserved beside the pages.

Thread-safety: the GenerationScheduler's worker thread is the only caller;
a lock still guards acquire/release so `stats()` from other threads is
consistent.
"""

import contextlib
import threading

import numpy as np

__all__ = ["PagedKVPool", "PoolExhausted", "PrefixCache"]

SCRATCH_PAGE = 0


class PoolExhausted(RuntimeError):
    """No free slot or not enough free pages for the reservation."""


class PagedKVPool:
    def __init__(self, n_pages, page_size, max_slots, max_pages_per_slot,
                 storage_dtype="float32", row_bytes=0):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is scratch)")
        if page_size < 1 or max_slots < 1 or max_pages_per_slot < 1:
            raise ValueError("page_size/max_slots/max_pages_per_slot must be >= 1")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        self.max_pages_per_slot = int(max_pages_per_slot)
        # storage mode is bookkeeping only (the device arrays live with the
        # engine): "int8" pools store per-row levels + f32 per-page scale
        # vectors at ~1/4 the f32 bytes per token, so the same HBM budget
        # funds >= 2x the pages/slots. row_bytes is the caller-computed
        # device bytes per pooled token row across all layers (levels +
        # scales), surfaced through stats() for the monitor's kv-pool row.
        self.storage_dtype = str(storage_dtype)
        self.row_bytes = int(row_bytes)
        # device bytes of the fixed per-slot state rows beside the pages (a
        # model with conv or recurrent layers; the engine sets it), scratch
        # row included
        self.state_bytes = 0
        self._lock = threading.Lock()
        # LIFO free lists: hottest pages get reused first (best for any
        # future device-side page cache locality)
        self._free_pages = list(range(1, self.n_pages))
        self._free_slots = list(range(self.max_slots - 1, -1, -1))
        self._tables = {}  # slot -> np.int32 [max_pages_per_slot]
        self._refs = {}  # page -> live reference count (slots + prefix cache)

    @property
    def pool_rows(self):
        return self.n_pages * self.page_size

    def pages_for(self, n_positions):
        """Pages needed to hold `n_positions` cached tokens."""
        return -(-int(n_positions) // self.page_size)

    def can_admit(self, n_positions, n_shared=0):
        need = max(0, self.pages_for(n_positions) - int(n_shared))
        with self._lock:
            return (
                bool(self._free_slots)
                and need <= len(self._free_pages)
                and self.pages_for(n_positions) <= self.max_pages_per_slot
            )

    def acquire(self, n_positions, shared_pages=()):
        """Reserve a slot + pages for a request whose cache will hold at most
        `n_positions` tokens. `shared_pages` (prefix-cache hits, already
        alive) fill the leading table entries and gain a reference each;
        only the remainder is drawn from the free list. Returns
        (slot, block_table) where block_table is the slot's np.int32
        [max_pages_per_slot] page list, scratch-0 padded. Raises
        PoolExhausted when it can't."""
        need = self.pages_for(n_positions)
        shared = [int(p) for p in shared_pages]
        if need > self.max_pages_per_slot:
            raise PoolExhausted(
                "%d positions need %d pages > max_pages_per_slot %d"
                % (n_positions, need, self.max_pages_per_slot)
            )
        if len(shared) > need:
            raise ValueError("more shared pages than the reservation needs")
        need_new = need - len(shared)
        with self._lock:
            if not self._free_slots:
                raise PoolExhausted("no free decode slot")
            if need_new > len(self._free_pages):
                raise PoolExhausted(
                    "need %d pages, %d free" % (need_new, len(self._free_pages))
                )
            slot = self._free_slots.pop()
            table = np.full(self.max_pages_per_slot, SCRATCH_PAGE, np.int32)
            for i, pid in enumerate(shared):
                if self._refs.get(pid, 0) < 1:
                    raise ValueError("shared page %d is not alive" % pid)
                table[i] = pid
                self._refs[pid] += 1
            for i in range(need_new):
                pid = self._free_pages.pop()
                table[len(shared) + i] = pid
                self._refs[pid] = 1
            self._tables[slot] = table
            return slot, table

    def release(self, slot):
        """Retire a slot: drop one reference per page; pages nobody else
        holds return to the free list for reuse."""
        with self._lock:
            table = self._tables.pop(slot, None)
            if table is None:
                return
            for p in table:
                if p != SCRATCH_PAGE:
                    self._unref_locked(int(p))
            self._free_slots.append(slot)

    def pin_pages(self, pages):
        """Add one reference to each (alive) page — the prefix cache's hold."""
        with self._lock:
            for p in pages:
                p = int(p)
                if self._refs.get(p, 0) < 1:
                    raise ValueError("pin of dead page %d" % p)
                self._refs[p] += 1

    def unpin_pages(self, pages):
        """Drop one reference from each page; frees those reaching zero."""
        with self._lock:
            for p in pages:
                self._unref_locked(int(p))

    def page_refcount(self, page):
        with self._lock:
            return self._refs.get(int(page), 0)

    def count_sole_refs(self, pages):
        """How many of `pages` have exactly one reference, under one lock
        (the prefix cache asks this of every page it holds, every pass)."""
        with self._lock:
            refs = self._refs
            return sum(1 for p in pages if refs.get(p, 0) == 1)

    def _unref_locked(self, page):
        c = self._refs.get(page, 0) - 1
        if c > 0:
            self._refs[page] = c
        else:
            self._refs.pop(page, None)
            self._free_pages.append(page)

    def block_table(self, slot):
        with self._lock:
            t = self._tables.get(slot)
            return None if t is None else t.copy()

    def stats(self):
        with self._lock:
            in_use = (self.n_pages - 1) - len(self._free_pages)
            slots = self.max_slots - len(self._free_slots)
            return {
                "pages_total": self.n_pages - 1,  # scratch excluded
                "pages_in_use": in_use,
                "pages_shared": sum(1 for c in self._refs.values() if c > 1),
                "slots_total": self.max_slots,
                "slots_in_use": slots,
                "slot_occupancy": slots / float(self.max_slots),
                "storage_dtype": self.storage_dtype,
                "resident_bytes": self.row_bytes * self.n_pages * self.page_size,
                "state_bytes": self.state_bytes,
            }


class _PrefixNode:
    __slots__ = ("page", "stamp", "state", "uid", "parent", "depth", "children")

    def __init__(self, page, stamp, state, uid, parent):
        self.page = page
        self.stamp = stamp
        self.state = state  # the sequence state at this boundary, if kept
        self.uid = uid  # what its children's keys name
        self.parent = parent  # the node one page up; None at depth 1
        self.depth = 1 if parent is None else parent.depth + 1
        self.children = 0


def _no_phase(what):
    return contextlib.nullcontext()


class PrefixCache:
    """Prompt-token trie over immutable full KV pages (module docstring)."""

    def __init__(self, pool, capacity_pages=None, state_bytes=0,
                 snapshot_bytes=None):
        self.pool = pool
        # state_bytes > 0: the model keeps a fixed per-sequence state beside
        # its pages, state_bytes a sequence, and only a boundary that
        # carries a snapshot of it can be hit. The reserve snapshot_bytes is
        # the device memory the snapshots may take together (default: four a
        # slot), so it holds as many as fit, not a count.
        self.state_bytes = int(state_bytes)
        if snapshot_bytes is None:
            snapshot_bytes = 4 * pool.max_slots * self.state_bytes
        self.snapshot_bytes = int(snapshot_bytes)
        self._n_states = 0  # nodes that carry a snapshot now
        self.states_taken = 0
        self.states_hit = 0
        self.states_evicted = 0
        # default cap: the whole pool minus one slot's worst case, so the
        # cache alone can never wedge admission even before eviction runs
        if capacity_pages is None:
            capacity_pages = max(
                0, pool.n_pages - 1 - pool.max_pages_per_slot
            )
        self.capacity_pages = int(capacity_pages)
        self._lock = threading.Lock()
        # (parent's uid, or 0 at depth 1; the page's tokens) -> _PrefixNode
        self._nodes = {}
        self._next_uid = 1
        self._clock = 0
        self.hits = 0  # lookups that found >= 1 page
        self.misses = 0
        self.pages_hit = 0
        self.pages_eligible = 0
        self.evictions = 0
        # phase("lookup" | "insert" | "evict") -> a context around that entry
        # point's work. Whoever serves through the cache hands it its own
        # (GenerationEngine: the spans fluid.prefix.* and its model's
        # prefix_*_ms, which the cache could not name); without, nothing is
        # recorded
        self.phase = _no_phase

    def lookup(self, prompt):
        """lookup_state()'s pages alone: all a model without a state needs."""
        return self.lookup_state(prompt)[0]

    def lookup_state(self, prompt):
        """(pages, state, matched): page ids for the longest cached prefix of
        `prompt`, capped so at least the final prompt token is always
        prefilled (its hidden state produces the first sampled logits). Each
        returned page is PINNED (+1 reference) so an eviction between lookup
        and acquire can never free it — the caller unpins once acquire() has
        taken the slot's own reference (or on admission failure). Counters
        feed the gen/prefix_hit_rate telemetry.

        With a per-sequence state (state_bytes > 0) the pages stop at the
        deepest boundary that carries a snapshot, `state` is that snapshot
        (None without a hit) and `matched` >= len(pages) is how many pages
        of the prompt the trie holds at all: a boundary other prompts share,
        where this prompt's prefill should leave a snapshot."""
        with self.phase("lookup"):
            ps = self.pool.page_size
            prompt = tuple(int(t) for t in prompt)
            eligible = (len(prompt) - 1) // ps
            found, state = [], None
            with self._lock:
                self._clock += 1
                parent = 0
                for i in range(eligible):
                    node = self._nodes.get((parent, prompt[i * ps:(i + 1) * ps]))
                    if node is None:
                        break
                    node.stamp = self._clock
                    found.append(node)
                    parent = node.uid
                matched = len(found)
                if self.state_bytes:
                    usable = 0
                    for i in range(matched, 0, -1):
                        if found[i - 1].state is not None:
                            usable, state = i, found[i - 1].state
                            self.states_hit += 1
                            break
                    del found[usable:]
                pages = [node.page for node in found]
                self.pages_eligible += eligible
                self.pages_hit += len(pages)
                if pages:
                    self.hits += 1
                elif eligible:
                    self.misses += 1
            if pages:
                self.pool.pin_pages(pages)
            return pages, state, matched

    def insert(self, prompt, table, states=None):
        """Publish a finished prefill's full prompt pages into the trie.
        Valid by the immutability invariant: pages 0..len(prompt)//ps - 1
        hold exactly the prompt tokens' K/V and nothing ever rewrites
        them. Already-cached depths are left alone, but for a snapshot they
        lack: `states` is {tokens: the sequence state after that many prompt
        tokens}, what this prefill left at its page-aligned chunk ends."""
        with self.phase("insert"):
            states = states or {}
            ps = self.pool.page_size
            prompt = tuple(int(t) for t in prompt)
            n_full = len(prompt) // ps
            added = 0
            with self._lock:
                self._clock += 1
                parent = parent_key = None
                for i in range(n_full):
                    key = (parent.uid if parent else 0, prompt[i * ps:(i + 1) * ps])
                    state = states.get((i + 1) * ps)
                    node = self._nodes.get(key)
                    if node is not None:
                        node.stamp = self._clock
                        if state is not None and node.state is None:
                            node.state = state
                            self.states_taken += 1
                            self._n_states += 1
                        parent, parent_key = node, key
                        continue
                    if len(self._nodes) >= self.capacity_pages:
                        if not self._evict_locked(1):
                            break
                        # the path just walked is the warmest there is, but its
                        # end is a leaf until this page hangs from it: had the
                        # eviction taken it, a child would be unreachable
                        if parent is not None and (
                                self._nodes.get(parent_key) is not parent):
                            break
                    page = int(table[i])
                    if page == SCRATCH_PAGE:
                        break
                    self.pool.pin_pages([page])
                    node = _PrefixNode(page, self._clock, state, self._next_uid, parent)
                    self._next_uid += 1
                    if parent is not None:
                        parent.children += 1
                    self._nodes[key] = node
                    if state is not None:
                        self.states_taken += 1
                        self._n_states += 1
                    added += 1
                    parent, parent_key = node, key
                if self._n_states * self.state_bytes > self.snapshot_bytes:
                    self._cap_states_locked()
            return added

    def _cap_states_locked(self):
        """Drop the coldest snapshots until those left fit the reserve;
        their nodes and pages stay (a prompt can still match through them)."""
        kept = [n for n in self._nodes.values() if n.state is not None]
        over = len(kept) - self.snapshot_bytes // max(self.state_bytes, 1)
        for node in sorted(kept, key=lambda n: n.stamp)[:max(over, 0)]:
            node.state = None
            self.states_evicted += 1
            self._n_states -= 1

    def evict_for(self, n_pages):
        """Free up to `n_pages` unreferenced cached pages (LRU). Returns the
        number actually evicted — admission retries when > 0."""
        with self._lock:
            return self._evict_locked(n_pages)

    def _evict_locked(self, n_pages):
        with self.phase("evict"):
            # children before parents: a deeper node is at least as cold as the
            # node above it, and dropping a parent first would leave unreachable
            # descendants pinned
            order = sorted(
                self._nodes.items(), key=lambda kv: (kv[1].stamp, -kv[1].depth)
            )
            evicted = 0
            for key, node in order:
                if evicted >= n_pages:
                    break
                # only pages no slot is reading (our pin is the sole reference)
                if self.pool.page_refcount(node.page) != 1:
                    continue
                if node.children:
                    continue  # has live descendants; they sort earlier anyway
                del self._nodes[key]
                if node.parent is not None:
                    node.parent.children -= 1
                self.pool.unpin_pages([node.page])
                if node.state is not None:
                    self.states_evicted += 1
                    self._n_states -= 1
                self.evictions += 1
                evicted += 1
            return evicted

    def reclaimable(self):
        """Cached pages only the trie holds — evictable on demand (the
        scheduler counts these as available when budgeting admissions)."""
        with self._lock:
            return self.pool.count_sole_refs(
                [n.page for n in self._nodes.values()])

    def clear(self):
        with self._lock:
            for node in self._nodes.values():
                self.pool.unpin_pages([node.page])
            n = len(self._nodes)
            self._nodes.clear()
            self._n_states = 0
            return n

    def stats(self):
        with self._lock:
            elig = self.pages_eligible
            n_states = self._n_states
            return {
                "cached_states": n_states,
                "state_bytes": n_states * self.state_bytes,
                "state_bytes_reserved": self.snapshot_bytes,
                "states_taken": self.states_taken,
                "states_hit": self.states_hit,
                "states_evicted": self.states_evicted,
                "cached_pages": len(self._nodes),
                "capacity_pages": self.capacity_pages,
                "lookups_hit": self.hits,
                "lookups_miss": self.misses,
                "pages_hit": self.pages_hit,
                "pages_eligible": elig,
                "hit_rate": (self.pages_hit / elig) if elig else 0.0,
                "evictions": self.evictions,
            }
