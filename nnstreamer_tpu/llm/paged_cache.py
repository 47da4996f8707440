"""Paged KV cache: fixed-size blocks + free-list allocator.

The streaming transformer's `init_cache` reserves (B, max_len) per
sequence up front — fine for one pinned pipeline, hopeless for serving:
a 128-slot server at max_len=2048 would reserve 256k token slots while
typical occupancy is a fraction of that. Paging (vLLM's PagedAttention
idea, PAPERS.md) decouples the two: the pool holds `num_blocks` blocks
of `block_size` token slots each, and every sequence owns an ordered
per-sequence *block table* mapping its positions onto pool blocks.
Memory is bounded by the pool, admission is bounded by free blocks, and
fragmentation is impossible by construction (any free block serves any
sequence — the table, not adjacency, provides ordering).

Block 0 is reserved as the scratch block: padding rows of a bucketed
decode batch and the padded tail of a bucketed prefill write there, so
pow2 padding never corrupts a live sequence's cache.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from nnstreamer_tpu.core.log import get_logger

log = get_logger("llm.cache")

#: pool block index reserved for padding writes (never allocated)
SCRATCH_BLOCK = 0


class BlockAllocator:
    """Free-list allocator over the pool's block indices.

    All-or-nothing `alloc(n)`: a request either gets its whole block
    set or stays queued (None) — partial grants would deadlock two
    half-admitted requests against each other. Single-threaded by
    design: the engine owns it from one scheduler thread.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"paged pool needs >= 2 blocks (1 scratch + 1 usable), "
                f"got {num_blocks}")
        self.num_blocks = num_blocks
        # LIFO free list: recently-freed (cache-warm) blocks reused first
        self._free: List[int] = list(range(num_blocks - 1, SCRATCH_BLOCK, -1))
        self._owner: Dict[int, object] = {}
        self.high_water = 0
        self.alloc_calls = 0
        self.failed_allocs = 0

    @property
    def total(self) -> int:
        """Allocatable blocks (the scratch block is never granted)."""
        return self.num_blocks - 1

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def used(self) -> int:
        return self.total - len(self._free)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int, owner: object = None) -> Optional[List[int]]:
        """Grant `n` blocks or None (caller queues — never crashes)."""
        self.alloc_calls += 1
        if n < 0:
            raise ValueError(f"alloc({n}): negative block count")
        if n > len(self._free):
            self.failed_allocs += 1
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._owner[b] = owner
        if self.used > self.high_water:
            self.high_water = self.used
        return blocks

    def free_blocks(self, blocks: List[int]) -> None:
        for b in blocks:
            if b not in self._owner:
                raise ValueError(
                    f"free of unallocated block {b} (double free, or a "
                    f"block the allocator never granted)")
            del self._owner[b]
            self._free.append(b)

    def stats(self) -> dict:
        return {
            "blocks_total": self.total,
            "blocks_free": self.free,
            "blocks_used": self.used,
            "blocks_high_water": self.high_water,
            "utilization": round(self.used / self.total, 4),
            "alloc_calls": self.alloc_calls,
            "failed_allocs": self.failed_allocs,
        }


def idx_pack(block_size: int, idx_dim: int) -> int:
    """Slots of the indexer pool that share one row: as many as fit 128
    values and divide the block."""
    if idx_dim <= 0:
        return 1
    p = max(1, min(int(block_size), 128 // int(idx_dim)))
    while block_size % p:
        p -= 1
    return p


class PagedKVCache:
    """The device-resident block pool + its allocator.

    k/v pools: (n_layers, num_blocks, block_size, n_kv, head_dim).
    A model with an indexer (llm/sparse_moe.py) keeps a third kind of
    state in the same blocks, under the same tables and allocator: the
    indexer's keys, `idx`: block_size slots of idx_dim values a block
    and layer, held as (n_layers, num_blocks, block_size // idx_pack,
    idx_pack * idx_dim) with `idx_pack` neighbouring slots side by side
    in one row of up to 128 values (a 64-wide minor dimension made
    XLA:TPU re-lay the whole pool for every layer of a step; slot s of
    a block is row s // idx_pack, values (s % idx_pack) * idx_dim
    onward). `idx_dim=0` (every dense model) makes no third pool.

    `dtype` is the type the model computes K, V and the indexer's keys
    in, and the type the pools keep them in: every value a step writes
    is a `dtype` value already, so a wider pool would hold zeros beside
    it and every read would move them.

    The pools live here as plain jax arrays and are threaded through the
    executor's donated jit calls (write-in-place on device); this class
    only owns layout and accounting, never math.
    """

    def __init__(self, *, num_blocks: int, block_size: int, n_layers: int,
                 n_kv: int, head_dim: int, idx_dim: int = 0, dtype=None,
                 placer=None):
        import jax.numpy as jnp

        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.n_layers = int(n_layers)
        self.n_kv = int(n_kv)
        self.head_dim = int(head_dim)
        self.dtype = jnp.dtype(jnp.float32 if dtype is None else dtype)
        shape = (self.n_layers, self.num_blocks, self.block_size,
                 self.n_kv, self.head_dim)
        self.k = jnp.zeros(shape, self.dtype)
        self.v = jnp.zeros(shape, self.dtype)
        self.idx_dim = int(idx_dim)
        self.idx_pack = idx_pack(self.block_size, self.idx_dim)
        self.idx = jnp.zeros(
            (self.n_layers, self.num_blocks,
             self.block_size // self.idx_pack,
             self.idx_pack * self.idx_dim), self.dtype) \
            if self.idx_dim else None
        if placer is not None:
            # sharded serving hands us a device-placement closure (pool
            # sharded along the kv-head axis next to the projections —
            # serving/sharding.kv_pool_placer); allocator/table logic is
            # untouched, only where the bytes live changes
            self.k = placer(self.k)
            self.v = placer(self.v)
        self.allocator = BlockAllocator(self.num_blocks)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold `n_tokens` token slots."""
        return max(1, -(-int(n_tokens) // self.block_size))

    @property
    def tokens_capacity(self) -> int:
        return self.allocator.total * self.block_size

    def pools(self) -> tuple:
        """The pools there are: (k, v), and the indexer's keys after
        them where the model has an indexer."""
        return (self.k, self.v) if self.idx is None \
            else (self.k, self.v, self.idx)

    def set_pools(self, pools) -> None:
        """Keep the pools a donating jit handed back, in `pools()` order."""
        self.k, self.v, *rest = pools
        if rest:
            self.idx, = rest

    @property
    def block_bytes(self) -> int:
        """Bytes one block holds over all layers and all pools."""
        per_slot = 2 * self.n_kv * self.head_dim + self.idx_dim
        return (self.n_layers * self.block_size * per_slot
                * self.dtype.itemsize)

    def resident_bytes(self) -> int:
        return sum(int(p.nbytes) for p in self.pools())

    def stats(self) -> dict:
        out = self.allocator.stats()
        out["block_size"] = self.block_size
        out["tokens_capacity"] = self.tokens_capacity
        out["pools"] = 2 if self.idx is None else 3
        out["block_bytes"] = self.block_bytes
        return out
