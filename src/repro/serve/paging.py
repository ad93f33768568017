"""Paged KV cache: fixed-size blocks in a shared pool + per-sequence
block tables, with refcounted cross-sequence block sharing.

The device side lives in ``models/attention.py`` (``paged_view`` /
``cache_insert``'s paged branch): every per-layer cache buffer is shaped
``[num_blocks, block_size, ...]`` and a ``block_tables`` leaf ``[B,
max_blocks_per_seq]`` maps each sequence's logical blocks to physical
pool blocks (-1 = unallocated).  This module is the *host* side: a
refcounting allocator with double-booking checks, the prefix index that
lets many sequences share one physical block, and helpers to push
updated block tables into a cache tree.

Ownership / refcount / immutability invariants (enforced by the
asserts here and by ``tests/test_property_paging.py``):

  * every allocated block has >= 1 holders; a holder appears at most
    once per block (``free`` is a decref — the block is recycled only
    when the LAST holder releases it, so refcounts can never go
    negative and preempt-by-recompute can never yank a shared block out
    from under another sequence);
  * a block with more than one holder is IMMUTABLE: the scheduler only
    shares blocks that are completely filled with prompt/prefix KV, and
    every write (decode append, prefill chunk) lands at a position
    whose block is held by exactly one sequence.  Copy-on-write is
    "copy by recompute": a request whose prompt ends inside (or
    diverges inside) a cached block gets a fresh private block and
    prefills those tokens again — shared blocks are never written;
  * a shared block sits at the SAME logical index in every holder's
    table (the prefix key hashes the whole token chain from position
    0), so the device-side ``pos == logical index`` liveness rule holds
    for every sharer without per-sequence state.

Physical block 0 is reserved as the trash block: writes whose target is
out of range or unallocated (right-padded prefill chunks, idle batch
rows) are routed there by the device-side insert, and the view masks any
slot reached through a -1 table entry — so the trash block's contents
are never observable.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from repro.models.module import tree_map_with_path


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` KV entries."""
    return -(-max(n_tokens, 0) // block_size)


class BlockPool:
    """Refcounting free-list allocator over the shared block pool.

    Block 0 is reserved (trash); ``capacity`` counts usable blocks only.
    ``alloc`` hands out exclusive blocks (refcount 1); ``share`` adds a
    holder to an already-allocated block (prefix reuse); ``free``
    removes ONE holder and recycles the block only at refcount 0.
    Every transition is checked against the holder map so a block can
    never be double-booked, double-freed, or freed by a non-holder —
    the invariants the paged cache's correctness rests on.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: deque = deque(range(1, num_blocks))
        self._holders: Dict[int, List[object]] = {}   # block -> holder tags

    # ------------------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Usable blocks (excludes the reserved trash block)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Distinct allocated blocks (a shared block counts once)."""
        return self.capacity - len(self._free)

    def occupancy(self) -> float:
        return self.used_blocks / self.capacity

    def blocks_for(self, n_tokens: int) -> int:
        return blocks_for(n_tokens, self.block_size)

    # ------------------------------------------------------------------
    def alloc(self, owner, n: int = 1) -> Optional[List[int]]:
        """Allocate ``n`` exclusive blocks for ``owner``; None if
        insufficient (all-or-nothing, so a partial grab never strands
        blocks)."""
        if n > len(self._free):
            return None
        out = []
        for _ in range(n):
            b = self._free.popleft()
            assert b not in self._holders, f"double-booked block {b}"
            assert b != 0, "trash block leaked into the free list"
            self._holders[b] = [owner]
            out.append(b)
        return out

    def share(self, blocks: Sequence[int], owner) -> None:
        """Add ``owner`` as a holder of each already-allocated block
        (refcount + 1).  Shared blocks are immutable by contract — the
        scheduler only shares full, registered prefix blocks."""
        for b in blocks:
            hs = self._holders.get(b)
            assert hs, f"sharing unallocated block {b}"
            assert owner not in hs, f"owner {owner} already holds block {b}"
            hs.append(owner)

    def free(self, blocks: Sequence[int], owner) -> None:
        """Release ``owner``'s hold on each block (refcount - 1); a
        block returns to the free list only when its LAST holder frees
        it."""
        for b in blocks:
            hs = self._holders.get(b)
            assert hs is not None, f"double-free of block {b}"
            assert owner in hs, f"block {b} not held by {owner} " \
                                f"(holders: {hs})"
            hs.remove(owner)
            if not hs:
                del self._holders[b]
                self._free.append(b)

    # ------------------------------------------------------------------
    def refcount(self, block: int) -> int:
        return len(self._holders.get(block, ()))

    def writable(self, block: int, owner) -> bool:
        """The immutability predicate: only the sole holder may write."""
        return self._holders.get(block) == [owner]

    def owned_by(self, owner) -> List[int]:
        return [b for b, hs in self._holders.items() if owner in hs]

    def check(self) -> None:
        """Assert the pool's books balance (used in tests after every run)."""
        assert len(self._free) + len(self._holders) == self.capacity
        assert not (set(self._free) & set(self._holders))
        for b, hs in self._holders.items():
            assert len(hs) >= 1, f"allocated block {b} with no holders"
            assert len(hs) == len(set(map(id, hs))), \
                f"duplicate holder on block {b}"


# ---------------------------------------------------------------------------
# prefix cache: chain-hash index over block-aligned token chunks
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Entry:
    """One cached block: the KV of ``tokens`` at logical block depth
    ``depth`` under the chain identified by ``parent`` (None = block 0
    of a sequence)."""
    key: int
    parent: Optional[int]
    tokens: Tuple[int, ...]
    block: int
    depth: int
    children: Set[int] = dataclasses.field(default_factory=set)
    last_used: int = 0


class PrefixCache:
    """Prefix index: rolling hash of block-aligned token chunks -> live
    physical block, so admission can map a new request's prompt onto
    blocks that already hold its KV instead of scheduling prefill.

    Entries form a trie over token chunks: the key of block ``j`` is
    ``hash((key_of_block_{j-1}, tokens_of_block_j))`` — it therefore
    commits to EVERY token from position 0, which is what makes a hit
    safe: a cached block is only ever adopted at the same logical index
    it was written at, with the same full token history (each step also
    re-verifies the chunk's tokens, so a hash collision degrades to a
    miss, never a wrong adoption).

    The cache holds its own reference on every entry's block (it is a
    holder in the :class:`BlockPool` sense), which keeps prefixes WARM
    after the sequences that wrote them retire.  Eviction is
    LRU-leaf-first and only touches blocks whose sole holder is the
    cache (``refcount == 1``): blocks shared with live sequences are
    pinned.  ``evict`` runs on demand when the pool would otherwise be
    dry — the cache never starves real allocations.
    """

    def __init__(self, pool: BlockPool):
        self.pool = pool
        self.entries: Dict[int, _Entry] = {}
        self._roots: Set[int] = set()
        self._tick = 0
        self.evictions = 0

    def __len__(self) -> int:
        """Number of cached blocks (each entry pins exactly one)."""
        return len(self.entries)

    @staticmethod
    def _key(parent: Optional[int], chunk: Tuple[int, ...]) -> int:
        return hash((parent, chunk))

    def _touch(self, e: _Entry) -> None:
        self._tick += 1
        e.last_used = self._tick

    # ------------------------------------------------------------------
    def lookup(self, tokens, max_blocks: int):
        """Longest cached chain covering ``tokens`` (at most
        ``max_blocks`` full blocks).  Returns ``(blocks, last_key)``:
        the physical blocks to adopt (logical indices ``0..len-1``) and
        the chain key of the last one (None on a cold miss) — the
        caller threads ``last_key`` back into registration so the chain
        continues where the hit ended.  Touches LRU; does NOT take a
        reference (the caller shares the blocks while holding the GIL,
        before anything can evict)."""
        bs = self.pool.block_size
        blocks: List[int] = []
        parent: Optional[int] = None
        for j in range(max_blocks):
            chunk = tuple(int(t) for t in tokens[j * bs:(j + 1) * bs])
            if len(chunk) < bs:
                break
            key = self._key(parent, chunk)
            e = self.entries.get(key)
            # verify the chunk's tokens AND the walked chain's parent key:
            # with both, induction over j proves the full token history
            # matches, so a hash collision (same key, different prefix)
            # degrades to a miss instead of adopting foreign KV
            if e is None or e.tokens != chunk or e.parent != parent:
                break
            self._touch(e)
            blocks.append(e.block)
            parent = key
        return blocks, parent

    def cached_overlap(self, parent_key: Optional[int], tail) -> int:
        """Longest common token prefix between ``tail`` (the request's
        remaining tokens inside the first un-adopted block) and any
        cached sibling chunk under ``parent_key``.  A positive overlap
        is a copy-on-write event: a memcpy-CoW design would copy those
        slots into a private block; this engine recomputes them (same
        outcome — the shared block is never written)."""
        tail = [int(t) for t in tail]
        if not tail:
            return 0
        kids = self._roots if parent_key is None \
            else self.entries[parent_key].children
        best = 0
        for k in kids:
            cached = self.entries[k].tokens
            n = 0
            for a, b in zip(tail, cached):
                if a != b:
                    break
                n += 1
            best = max(best, n)
        return best

    def register(self, parent_key: Optional[int], chunk: Tuple[int, ...],
                 block: int) -> Optional[int]:
        """Index ``block`` as holding the KV of ``chunk`` under
        ``parent_key``'s chain; the cache takes a reference (the block
        survives its writer).  A duplicate key with identical tokens is
        a no-op returning the existing key (the writer keeps its
        private copy; future admissions dedup against the first).
        Returns None — the caller must stop registering this chain —
        on a key collision with DIFFERENT tokens or parent (lookup's
        verification already makes the collision unadoptable), and
        when ``parent_key``'s entry has been evicted (a dedup'd chain
        whose backing entry retired): continuing would create a root
        entry that lookup can never reach, pinning a block for nothing
        and polluting the CoW metrics."""
        assert len(chunk) == self.pool.block_size, "only full blocks cache"
        key = self._key(parent_key, chunk)
        e = self.entries.get(key)
        if e is not None:
            if e.tokens != chunk or e.parent != parent_key:
                return None
            self._touch(e)
            return key
        parent = None
        if parent_key is not None:
            parent = self.entries.get(parent_key)
            if parent is None:
                return None
        e = _Entry(key=key, parent=parent_key, tokens=tuple(chunk),
                   block=block, depth=0 if parent is None else
                   parent.depth + 1)
        self.pool.share([block], self)
        self.entries[key] = e
        self._touch(e)
        if parent is None:
            self._roots.add(key)
        else:
            parent.children.add(key)
        return key

    # ------------------------------------------------------------------
    def evictable(self) -> int:
        """Blocks the cache could ACTUALLY free on demand via iterated
        leaf-first eviction: an entry is freeable iff its block has no
        holder but the cache AND every child entry is freeable (evict()
        only drops childless entries, so a pinned descendant blocks its
        whole ancestor chain).  Counting every refcount-1 entry would
        overcount — dedup can leave a cache-only parent above a pinned
        child (refcounts are not non-increasing with depth) — and an
        optimistic budget here makes the scheduler over-admit and then
        fail allocations evict() cannot actually cover."""
        freeable: Dict[int, bool] = {}
        # children always sit one level deeper than their parent, so a
        # deepest-first sweep sees every child before its parent
        for e in sorted(self.entries.values(), key=lambda e: -e.depth):
            freeable[e.key] = (self.pool.refcount(e.block) == 1 and
                               all(freeable[k] for k in e.children))
        return sum(freeable.values())

    def _drop(self, e: _Entry) -> None:
        del self.entries[e.key]
        if e.parent is None:
            self._roots.discard(e.key)
        else:
            parent = self.entries.get(e.parent)
            if parent is not None:
                parent.children.discard(e.key)
        self.pool.free([e.block], self)

    def evict(self, n: int) -> int:
        """Free up to ``n`` blocks, LRU-leaf-first, skipping blocks
        still shared with live sequences.  Returns blocks actually
        freed."""
        freed = 0
        while freed < n:
            best = None
            for e in self.entries.values():
                if e.children or self.pool.refcount(e.block) != 1:
                    continue
                if best is None or e.last_used < best.last_used:
                    best = e
            if best is None:
                break
            self._drop(best)
            freed += 1
        self.evictions += freed
        return freed

    def clear(self) -> None:
        """Release every cache reference (shared blocks stay allocated
        for their sequences).  After a drained engine clears its cache,
        the pool is fully free — the invariant the property tests close
        the loop on."""
        for e in list(self.entries.values()):
            self.pool.free([e.block], self)
        self.entries.clear()
        self._roots.clear()


# ---------------------------------------------------------------------------
# cache-tree helpers
# ---------------------------------------------------------------------------


def set_block_tables(cache, tables):
    """Return ``cache`` with every ``block_tables`` leaf set to ``tables``.

    ``tables``: int32 [B, max_blocks_per_seq] (np or jnp).  Scan-stacked
    layer caches carry a leading layers axis on every leaf; the tables
    are broadcast across it (all layers share one block table).
    """
    if isinstance(tables, np.ndarray):
        # a private copy: on the CPU backend jnp.asarray may alias a host
        # array zero-copy, and the engine rewrites its table buffer while
        # the step that reads this one can still be in flight
        tables = np.array(tables, np.int32)
    tables = jnp.asarray(tables, jnp.int32)

    def fix(path, leaf):
        if path and path[-1] == "block_tables":
            if leaf.ndim == tables.ndim + 1:          # scan-stacked layers
                # batch may differ from the leaf's (single-row prefill
                # slices), so rebuild the shape from the new tables
                return jnp.broadcast_to(tables[None],
                                        (leaf.shape[0], *tables.shape))
            return tables
        return leaf
    return tree_map_with_path(fix, cache)
