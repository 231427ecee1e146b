"""Paged KV-cache plane: block-pool allocator + radix prefix cache.

The dense engine pre-allocates a ``(max_batch, max_seq)`` KV cache per
replica, so memory — not compute — caps concurrency, and identical
prompt prefixes (multi-turn chat, shared system prompts) are re-prefilled
on every request. This module provides the bookkeeping half of the paged
alternative (vLLM-style paging + SGLang-style radix reuse):

  * ``BlockPool`` — a fixed population of ``block_size``-token KV blocks
    with refcounts. Requests lease blocks; sharing is a refcount bump,
    not a copy. The actual KV tensors live in the engine's pool arrays
    (``models.transformer.init_paged_cache``); block ids index them.
  * ``RadixPrefixCache`` — a radix tree over full token blocks mapping
    prompt prefixes to cached KV blocks. A new request walks the tree,
    leases every matched block (refcount++) and prefills only the
    uncached suffix. Completed sequences are inserted back, so multi-turn
    histories and shared system prompts hit. Leaf blocks referenced only
    by the cache are evictable (LRU) when the pool runs dry.

Copy-on-write: shared blocks are read-only. When a request must append
into a partially-reused block (its prompt ends mid-block inside a cached
run), the engine allocates a fresh block, copies the shared contents and
writes there — ``BlockPool`` only tracks the refcounts; the data copy is
a jitted engine function.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from repro.obs.trace import phase


class PoolExhausted(RuntimeError):
    """No free block available (and nothing evictable)."""


class BlockPool:
    """Fixed-size population of KV blocks with refcounted ownership."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks <= 0 or block_size <= 0:
            raise ValueError("num_blocks and block_size must be positive")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref = [0] * num_blocks
        self.version = 0               # bumped on every refcount change
        # actual device bytes one block occupies across the engine's pool
        # arrays (k + v + int8 scales), set by the owning engine from the
        # pool tensors' nbytes — int8 pools land at quantized width.
        self.bytes_per_block: int = 0

    # -- inspection ----------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def used_frac(self) -> float:
        return 1.0 - len(self._free) / self.num_blocks

    @property
    def capacity_bytes(self) -> int:
        return self.num_blocks * self.bytes_per_block

    @property
    def used_bytes(self) -> int:
        return (self.num_blocks - len(self._free)) * self.bytes_per_block

    @property
    def free_bytes(self) -> int:
        return len(self._free) * self.bytes_per_block

    def refcount(self, bid: int) -> int:
        return self._ref[bid]

    # -- allocate / share / release ------------------------------------------
    def alloc(self) -> int:
        """Take one free block (refcount 1). Raises ``PoolExhausted``."""
        if not self._free:
            raise PoolExhausted(f"all {self.num_blocks} KV blocks in use")
        bid = self._free.pop()
        self._ref[bid] = 1
        self.version += 1
        return bid

    def alloc_many(self, n: int) -> List[int]:
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} KV blocks, only {len(self._free)} free")
        return [self.alloc() for _ in range(n)]

    def incref(self, bid: int) -> None:
        assert self._ref[bid] > 0, f"incref on free block {bid}"
        self._ref[bid] += 1
        self.version += 1

    def decref(self, bid: int) -> bool:
        """Drop one reference; returns True if the block was freed."""
        assert self._ref[bid] > 0, f"decref on free block {bid}"
        self._ref[bid] -= 1
        self.version += 1
        if self._ref[bid] == 0:
            self._free.append(bid)
            return True
        return False


@dataclass
class _RadixNode:
    """One full KV block of tokens. Edge key = that block's token tuple."""
    key: Tuple[int, ...]
    block: int
    parent: Optional["_RadixNode"]
    children: Dict[Tuple[int, ...], "_RadixNode"] = field(default_factory=dict)
    t_access: int = 0


@dataclass
class PrefixStats:
    lookups: int = 0
    lookup_tokens: int = 0
    hit_tokens: int = 0
    inserted_blocks: int = 0
    evicted_blocks: int = 0

    @property
    def hit_rate(self) -> float:
        return self.hit_tokens / self.lookup_tokens if self.lookup_tokens else 0.0


class RadixPrefixCache:
    """Block-granular radix tree over token sequences.

    Nodes hold exactly one FULL block (``block_size`` tokens); partial
    tail blocks are never shared directly — a request that needs part of
    a cached block goes through the engine's copy-on-write path. The
    cache holds one refcount on every registered block; ``match`` takes
    an additional lease per matched block on behalf of the caller.
    """

    def __init__(self, pool: BlockPool):
        self.pool = pool
        self.block_size = pool.block_size
        self._root = _RadixNode(key=(), block=-1, parent=None)
        self._clock = 0
        self._by_block: Dict[int, _RadixNode] = {}
        self.stats = PrefixStats()
        self._evictable_memo: Tuple[int, int] = (-1, 0)   # (pool.version, n)

    def __len__(self) -> int:
        return len(self._by_block)

    # -- lookup ---------------------------------------------------------
    def _walk(self, tokens: Sequence[int], touch: bool) -> List[_RadixNode]:
        bs = self.block_size
        node, path = self._root, []
        for i in range(0, len(tokens) - bs + 1, bs):
            child = node.children.get(tuple(tokens[i:i + bs]))
            if child is None:
                break
            if touch:
                self._clock += 1
                child.t_access = self._clock
            path.append(child)
            node = child
        return path

    def peek(self, tokens: Sequence[int]) -> int:
        """Matched-prefix length in tokens, without taking leases."""
        return len(self._walk(tokens, touch=False)) * self.block_size

    def match(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """Longest cached prefix of ``tokens`` in full blocks.

        Returns ``(block_ids, n_tokens)``; every returned block carries a
        new lease (refcount++) the caller must ``decref`` when done.
        """
        path = self._walk(tokens, touch=True)
        blocks = [n.block for n in path]
        for bid in blocks:
            self.pool.incref(bid)
        self.stats.lookups += 1
        self.stats.lookup_tokens += len(tokens)
        self.stats.hit_tokens += len(blocks) * self.block_size
        return blocks, len(blocks) * self.block_size

    # -- registration ---------------------------------------------------
    def insert(self, tokens: Sequence[int], blocks: Sequence[int]) -> int:
        """Register ``blocks[i]`` as holding KV for tokens
        ``[i*bs, (i+1)*bs)``. Only full blocks may be passed. Existing
        nodes win (first writer keeps its block — both hold identical
        KV); new nodes take one cache refcount. Returns #registered."""
        bs = self.block_size
        assert len(blocks) * bs <= len(tokens)
        node, added = self._root, 0
        for i, bid in enumerate(blocks):
            key = tuple(tokens[i * bs:(i + 1) * bs])
            child = node.children.get(key)
            if child is None:
                self._clock += 1
                child = _RadixNode(key=key, block=bid, parent=node,
                                   t_access=self._clock)
                node.children[key] = child
                self._by_block[bid] = child
                self.pool.incref(bid)
                added += 1
            node = child
        self.stats.inserted_blocks += added
        return added

    # -- eviction -------------------------------------------------------
    def evictable_blocks(self) -> int:
        """Blocks reclaimable by cascading LRU leaf eviction: nodes whose
        entire subtree is referenced only by the cache. Single O(n) DFS,
        memoized on the pool's refcount version — the scheduler polls
        this on its admission hot path, usually with nothing changed
        (tree mutations always move a refcount, so the pool version
        covers insert/evict too)."""
        if self._evictable_memo[0] == self.pool.version:
            return self._evictable_memo[1]

        def walk(n: _RadixNode):
            total, all_free = 0, True
            for c in n.children.values():
                t, ok = walk(c)
                total += t
                all_free &= ok
            if all_free and self.pool.refcount(n.block) == 1:
                return total + 1, True
            return total, False

        n = sum(walk(c)[0] for c in self._root.children.values())
        self._evictable_memo = (self.pool.version, n)
        return n

    def evict(self, n: int) -> int:
        """Free up to ``n`` blocks, LRU leaves first. Returns #freed.
        One leaf scan up front; parents that become evictable leaves
        join the heap as their children go (no per-block rescans)."""
        with phase("kv.evict"):
            heap = [(node.t_access, node.block) for node in self._by_block.values()
                    if not node.children and self.pool.refcount(node.block) == 1]
            heapq.heapify(heap)
            freed = 0
            while freed < n and heap:
                _, bid = heapq.heappop(heap)
                victim = self._by_block[bid]
                parent = victim.parent
                self._remove(victim)
                freed += 1
                if (parent is not self._root and not parent.children
                        and self.pool.refcount(parent.block) == 1):
                    heapq.heappush(heap, (parent.t_access, parent.block))
            self.stats.evicted_blocks += freed
            return freed

    def _remove(self, node: _RadixNode) -> None:
        assert not node.children
        del node.parent.children[node.key]
        del self._by_block[node.block]
        self.pool.decref(node.block)

    def clear(self) -> int:
        """Drop every cache-only entry (live leases keep their blocks)."""
        return self.evict(len(self._by_block))
