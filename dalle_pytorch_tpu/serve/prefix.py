"""RadixPrefixCache: cross-request reuse of prompt prefill state.

``tile_prefill`` (models/dalle.py) rests on the property that a prompt's
K/V is continuation-independent — the prefill caches for a given token
sequence are a pure function of that sequence, whatever gets decoded
after it.  That same property makes prefill state *shareable across
requests*: two admissions with the same prompt can install copies of ONE
batch-1 prefill instead of running the transformer over the prompt
twice.  This module is the host-side index that makes the sharing safe:

* **A path-compressed radix tree over token tuples.**  Keys are the
  exact prompt token sequences; edges carry token *spans* (path
  compression keeps the node count proportional to the number of
  distinct prompts, not total tokens).  Lookup is exact-match: a hit
  returns the stored ``(first_logits, caches)`` device payload, which
  :meth:`SlotArena.admit` then rolls into a slot — admit does NOT donate
  its prefill arguments, so one payload can be installed into any number
  of slots.  (The tree — rather than a flat dict — is the structure the
  roadmap's shared-prefix *partial* reuse extends without re-keying:
  a future prefix hit is a walk that ends mid-edge.)
* **Refcount-guarded eviction.**  A payload acquired for a queued or
  running request is PINNED: ``acquire`` increments, the scheduler
  releases on retire/fail/preempt/stop, and eviction only ever considers
  entries at refcount zero (LRU order).  The cache may run over capacity
  while everything is pinned — correctness first, the capacity bound is
  advisory (tests/test_prefix.py pins the no-free-while-referenced
  property).
* **Observability in hardware units.**  Hits/misses and the prefill
  FLOPs a hit avoided (``utils.profiling.dalle_prefill_flops``)
  accumulate here; the scheduler exports them through ``stats()``,
  /metrics gauges and the telemetry stream obs_report aggregates.

Whole prompts only: a payload may hold recurrent state (a state-space
layer's ``(window, h)``, ``DALLEConfig.trunk``), which is the state *after
the prompt's last token* and cannot be cut back to a shorter prefix the way
a key/value cache can be sliced.  The exact-match lookup above is therefore
the only hit such a payload serves; a partial-prefix walk must not return
it.

Device memory: payloads are batch-1 caches — ``depth * 2 * heads *
seq_len * dim_head`` elements each (graftmem's ``serve-prefix`` row
budgets ``capacity`` of them).  The tree itself is host-side and tiny.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

from ..utils import locks

Key = Tuple[int, ...]


class _Node:
    """One radix-tree node: ``edge`` is the token span from the parent
    (empty only at the root), ``children`` keys by each child's first
    edge token, ``entry`` is the terminal payload record (None for pure
    interior nodes)."""

    __slots__ = ("edge", "children", "entry")

    def __init__(self, edge: Key = ()):
        self.edge = tuple(edge)
        self.children: Dict[int, "_Node"] = {}
        self.entry: Optional["_Entry"] = None


class _Entry:
    __slots__ = ("key", "payload", "refcount", "flops", "last_used")

    def __init__(self, key: Key, payload, flops: float, stamp: int):
        self.key = key
        self.payload = payload
        self.refcount = 0
        self.flops = flops
        self.last_used = stamp


def _common_prefix_len(a: Key, b: Key) -> int:
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


class RadixPrefixCache:
    """Refcounted radix tree of prompt-token tuples -> batch-1 prefill
    payloads.  Thread-safe: every public method takes the internal
    ``prefix`` lock, so multiple replica drivers (or the router's retry
    path racing a driver) can acquire/insert/release concurrently without
    corrupting refcounts or the tree.

    ``capacity`` bounds the number of RESIDENT payloads; eviction is LRU
    over refcount-zero entries only, so the bound is exceeded while more
    than ``capacity`` payloads are pinned by live requests (the arena
    itself bounds how many can be running, so the overshoot is bounded
    too).  ``prefill_flops`` is the per-prompt forward cost a hit
    avoids; pass ``utils.profiling.dalle_prefill_flops(cfg)``."""

    def __init__(self, capacity: int = 32, *, prefill_flops: float = 0.0):
        assert capacity >= 1, "a zero-capacity prefix cache is just 'off'"
        self.capacity = capacity
        self.prefill_flops = float(prefill_flops)
        self._lock = locks.TracedLock("prefix")
        self._root = _Node()
        self._entries: Dict[Key, _Entry] = {}  # iteration/LRU index
        self._stamp = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.flops_saved = 0.0

    # --- radix-tree internals --------------------------------------------

    def _find(self, key: Key) -> Optional[_Node]:
        """Exact-match walk: the node whose root-path spells ``key``, or
        None (including walks that end mid-edge)."""
        node, i = self._root, 0
        while i < len(key):
            child = node.children.get(key[i])
            if child is None:
                return None
            edge = child.edge
            if tuple(key[i:i + len(edge)]) != edge:
                return None  # diverges inside (or beyond) the edge
            i += len(edge)
            node = child
        return node if i == len(key) else None

    def _insert_node(self, key: Key) -> _Node:
        """The node for ``key``, splitting edges as needed (standard
        path-compressed insert)."""
        node, i = self._root, 0
        while i < len(key):
            child = node.children.get(key[i])
            if child is None:
                leaf = _Node(key[i:])
                node.children[key[i]] = leaf
                return leaf
            p = _common_prefix_len(tuple(key[i:]), child.edge)
            if p == len(child.edge):
                node, i = child, i + p
                continue
            # split child's edge at p: node -> mid -> child
            mid = _Node(child.edge[:p])
            child.edge = child.edge[p:]
            mid.children[child.edge[0]] = child
            node.children[key[i]] = mid
            if i + p == len(key):
                return mid
            leaf = _Node(key[i + p:])
            mid.children[key[i + p]] = leaf
            return leaf
        return node

    def _remove(self, key: Key) -> None:
        """Drop ``key``'s entry and prune/re-merge the path (keeps the
        tree path-compressed as entries churn)."""
        path = [self._root]
        node, i = self._root, 0
        while i < len(key):
            child = node.children.get(key[i])
            assert child is not None, "removing a key that was never stored"
            path.append(child)
            i += len(child.edge)
            node = child
        node.entry = None
        # prune empty leaves upward, then merge single-child interior nodes
        for parent, n in zip(reversed(path[:-1]), reversed(path[1:])):
            if n.entry is None and not n.children:
                del parent.children[n.edge[0]]
            elif n.entry is None and len(n.children) == 1 and n is not self._root:
                (only,) = n.children.values()
                only.edge = n.edge + only.edge
                parent.children[n.edge[0]] = only
            else:
                break

    # --- public API (scheduler-facing) ------------------------------------

    def acquire(self, tokens) -> Optional[object]:
        """Exact-match lookup that PINS on hit: returns the payload with
        its refcount incremented (caller must :meth:`release` exactly
        once), or None on miss.  Hit/miss and FLOPs-saved counters
        update here."""
        key = tuple(int(t) for t in tokens)
        with self._lock:
            node = self._find(key)
            if node is None or node.entry is None:
                self.misses += 1
                return None
            entry = node.entry
            entry.refcount += 1
            self._stamp += 1
            entry.last_used = self._stamp
            self.hits += 1
            self.flops_saved += entry.flops
            return entry.payload

    def insert(self, tokens, payload) -> object:
        """Store a freshly-computed prefill payload and pin it for the
        inserting request (refcount starts at 1 — the caller releases it
        like an acquire).  Runs LRU eviction of unpinned entries if over
        capacity.  Idempotent on key collision: keeps the resident
        payload and pins that instead (two racing misses on one prompt
        must not hold divergent device copies)."""
        key = tuple(int(t) for t in tokens)
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                existing.refcount += 1
                self._stamp += 1
                existing.last_used = self._stamp
                return existing.payload
            self._stamp += 1
            entry = _Entry(key, payload, self.prefill_flops, self._stamp)
            entry.refcount = 1
            self._insert_node(key).entry = entry
            self._entries[key] = entry
            self._evict_to_capacity_locked()
            return entry.payload

    def release(self, tokens) -> None:
        """Unpin one reference (retire/fail/preempt/stop all funnel
        here).  The payload stays resident for future hits until LRU
        eviction claims it."""
        key = tuple(int(t) for t in tokens)
        with self._lock:
            entry = self._entries.get(key)
            assert entry is not None, "release of an untracked prefix"
            assert entry.refcount > 0, "refcount underflow — double release"
            entry.refcount -= 1
            self._evict_to_capacity_locked()

    def _evict_to_capacity_locked(self) -> None:
        while len(self._entries) > self.capacity:
            victims = [e for e in self._entries.values() if e.refcount == 0]
            if not victims:
                return  # everything pinned: over-capacity is allowed
            victim = min(victims, key=lambda e: e.last_used)
            self._remove(victim.key)
            del self._entries[victim.key]
            self.evictions += 1

    def stats(self) -> dict:
        with self._lock:
            looked = self.hits + self.misses
            return {
                "entries": len(self._entries),
                "pinned": sum(1 for e in self._entries.values()
                              if e.refcount > 0),
                "capacity": self.capacity,
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": (self.hits / looked) if looked else 0.0,
                "evictions": self.evictions,
                "prefill_flops_saved": self.flops_saved,
            }
