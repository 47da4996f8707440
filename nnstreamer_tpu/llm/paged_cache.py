"""Paged KV cache: fixed-size blocks + free-list allocator.

The streaming transformer's `init_cache` reserves (B, max_len) per
sequence up front — fine for one pinned pipeline, hopeless for serving:
a 128-slot server at max_len=2048 would reserve 256k token slots while
typical occupancy is a fraction of that. Paging (vLLM's PagedAttention
idea, PAPERS.md) decouples the two: the pool holds `num_blocks` blocks
of `block_size` token slots each, and every sequence owns an ordered
per-sequence *block table* mapping its positions onto pool blocks.
Memory is bounded by the pool, and fragmentation is impossible by
construction (any free block serves any sequence — the table, not
adjacency, provides ordering).

A sequence holds the blocks its *written* context needs: those of its
prompt and of its first decode write at admission (`reserve`), one more
each time its write position reaches the end of its table (`grow`).
What bounds admission is therefore not the free list but the future:
`peak_demand` is the most blocks the admitted sequences will ever hold
together, each growing a position a decode launch until its budget ends,
and a sequence is admitted only if that peak, with it included, fits the
pool. So a growth grant cannot fail, and nothing is ever evicted,
stalled or recomputed for want of a block.

Block 0 is reserved as the scratch block: padding rows of a bucketed
decode batch and the padded tail of a bucketed prefill write there, so
pow2 padding never corrupts a live sequence's cache.

A model may keep a second kind of state, which is not paged: a *slot*
a sequence, under an allocator of its own, of a pool of fixed-size
float32 states (llm/hybrid_lm.py's linear layers, llm/delta_moe.py's
delta-rule layers) and of a pool of rows in the compute type, read whole
and in order and so kept by slot, not gathered by block: compressed
keys, one a block of the sequence's table (llm/hybrid_lm.py's sparse
layers), or the tails of short convolutions, the last few inputs of
each (llm/delta_moe.py, which keeps its latent layers' rows by block
beside them: two kinds of cache in one model). A sequence is admitted
with its blocks and its slot or with neither (`PagedKVCache.reserve`);
slot 0 is the scratch slot, as block 0 is the scratch block.

A model whose layers are of two kinds, some attending a window of the
newest positions and some the whole context (llm/window_moe.py), keeps a
second pair of K and V pools for its window layers, under an allocator
and a table a sequence of their own. Both tables are indexed by a
position's block; the window table's blocks wholly behind the window are
given back as the sequence advances (`trim`, after the launch that last
needed them was dispatched: the device runs its programs in order, so
whoever is granted the block next writes it after that launch has read
it) and their entries read the scratch block from then on. A sequence
therefore holds at most `window_cap(1)` window blocks while it decodes and
`window_cap(chunk)` while a chunk of its prompt is prefilled, whatever its
context, and `peak_demand` counts that cap. `reserve` admits against the
peak in both pools and grants both tables or neither.

A model whose attention is latent (llm/latent_moe.py) keeps no value a
head: `values=False` makes no V pool. Its `k` pool holds one compressed
row a token a layer (`n_kv` 1, `head_dim` the latent's width) and its
`idx` pool the one roped key all heads share, two tokens a 128-lane row
where the key is 64 wide (`idx_pack`), under the one table and allocator.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from nnstreamer_tpu.core.errors import BackendError
from nnstreamer_tpu.core.log import get_logger

log = get_logger("llm.cache")

#: pool block index reserved for padding writes (never allocated)
SCRATCH_BLOCK = 0


class BlockAllocator:
    """Free-list allocator over the pool's block indices.

    All-or-nothing `alloc(n)`: a grant is whole or refused (None).
    Sequences are granted their blocks piece by piece as they grow, and
    two half-grown sequences would deadlock against each other over the
    last free blocks if admission looked at the free list alone; what
    prevents it is `PagedKVCache.reserve`, which admits a sequence only
    if the admitted set's `peak_demand` fits the pool, so every growth
    grant it will ask for is there when asked. Single-threaded by
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


def peak_demand(rows, block_size: int, held: int = 0, cap: int = 0) -> int:
    """The most blocks a set of admitted sequences will hold together.
    `rows`: a (pos, k) pair a decoding sequence, its next write position
    and the decode launches it has left; each advances one position a
    launch, so at the j-th launch from now it holds the blocks of pos + j
    slots, and after its k-th it holds none. `held`: blocks counted at
    every launch, for sequences whose first launch is an unknown number
    of steps away (their whole lives). The demand only falls where a
    sequence ends, so the peak is at one of the rows' k. `cap` > 0: no
    sequence holds more than `cap` blocks (a window table's, whose blocks
    behind the window are given back); a capped sequence's holding stops
    growing but still only falls where it ends."""
    bs = int(block_size)
    cap = int(cap) or (1 << 62)
    peak = 0
    for j in {k for _, k in rows}:
        peak = max(peak, sum(min(-(-(pos + j) // bs), cap)
                             for pos, k in rows if k >= j))
    return int(held) + peak


def window_cap(window: int, block_size: int, span: int) -> int:
    """The most window blocks a sequence holds while `span` consecutive
    queries of it are computed at once (1: a decode step; a prompt
    chunk's width): those of the `window - 1` positions behind the first
    query and of the `span` queries, a block more where they straddle."""
    return -(-(int(window) - 1 + int(span)) // int(block_size)) + 1


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

    `n_layers` counts the layers that keep K and V (all of a dense
    model's; the sparse layers' KV heads of llm/hybrid_lm.py; the latent
    layers of llm/delta_moe.py). Two more pools serve those two
    families, both indexed by a sequence's *slot*, one of `state_slots`
    from `state_alloc` (slot 0 is scratch: padding rows and warm-up
    calls write there). `state_shape` (layers, heads, dk, dv): the state
    pool (layers, state_slots + 1, heads, dk, dv), float32 whatever
    `dtype` is. `row_shape` (layers, entries, width): the by-slot rows
    `slot_rows` (layers, state_slots + 1, entries, width) in `dtype`:
    compressed keys, entry m of a slot belonging to block m of its
    sequence's table, or convolutions' tails, the sequence's last
    `entries` inputs, oldest first.

    `window_layers` > 0 (llm/window_moe.py): a second pair of pools, `wk`
    and `wv`, (window_layers, window_blocks, block_size, n_kv, head_dim),
    for the layers that attend the newest `window` positions only, under
    `window_alloc` and a table a sequence of their own; `n_layers` then
    counts the layers that attend the whole context. Block 0 of these
    pools is their scratch block.

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
                 placer=None, state_shape: tuple = (),
                 row_shape: tuple = (), state_slots: int = 0,
                 window_layers: int = 0, window_blocks: int = 0,
                 window: int = 0, values: bool = True):
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
        self.v = jnp.zeros(shape, self.dtype) if values else None
        self.idx_dim = int(idx_dim)
        self.idx_pack = idx_pack(self.block_size, self.idx_dim)
        self.idx = jnp.zeros(
            (self.n_layers, self.num_blocks,
             self.block_size // self.idx_pack,
             self.idx_pack * self.idx_dim), self.dtype) \
            if self.idx_dim else None
        self.slot_rows = self.state = self.state_alloc = None
        if state_shape or row_shape:
            self.state_alloc = BlockAllocator(int(state_slots) + 1)

        def by_slot(shape, dtype):
            layers, *rest = (int(n) for n in shape)
            return jnp.zeros((layers, int(state_slots) + 1, *rest), dtype)

        if state_shape:
            self.state = by_slot(state_shape, jnp.float32)
        if row_shape:
            self.slot_rows = by_slot(row_shape, self.dtype)
        self.wk = self.wv = self.window_alloc = None
        self.window = int(window)
        if window_layers:
            if self.window < 1 or window_blocks < 2:
                raise ValueError(
                    f"window pools need a window >= 1 and >= 2 blocks, got "
                    f"window={window}, window_blocks={window_blocks}")
            wshape = (int(window_layers), int(window_blocks),
                      self.block_size, self.n_kv, self.head_dim)
            self.wk = jnp.zeros(wshape, self.dtype)
            self.wv = jnp.zeros(wshape, self.dtype)
            self.window_alloc = BlockAllocator(int(window_blocks))
        # window blocks given back behind the window, granted as rows
        # grew, and the largest window peak an admission was accepted at
        self.window_blocks_freed = 0
        self.window_blocks_grown = 0
        self.window_admit_peak = 0
        if placer is not None:
            # sharded serving hands us a device-placement closure (pool
            # sharded along the kv-head axis next to the projections —
            # serving/sharding.kv_pool_placer); allocator/table logic is
            # untouched, only where the bytes live changes
            self.k, self.v = placer(self.k), placer(self.v)
        self.allocator = BlockAllocator(self.num_blocks)
        # growth grants, and the largest peak an admission was accepted at
        self.blocks_grown = 0
        self.admit_peak = 0

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold `n_tokens` token slots."""
        return max(1, -(-int(n_tokens) // self.block_size))

    @property
    def tokens_capacity(self) -> int:
        return self.allocator.total * self.block_size

    _POOLS = ("k", "v", "idx", "slot_rows", "state", "wk", "wv")

    def pools(self) -> tuple:
        """The pools there are: k, v (where the model keeps values),
        then those the model adds, in the order indexer keys, the rows
        kept by slot, state, the window layers' K and V."""
        return tuple(p for p in (getattr(self, n) for n in self._POOLS)
                     if p is not None)

    def set_pools(self, pools) -> None:
        """Keep the pools a donating jit handed back, in `pools()` order."""
        rest = list(pools)
        for name in self._POOLS:
            if getattr(self, name) is not None:
                setattr(self, name, rest.pop(0))

    # -- admission over both kinds of state --------------------------------
    def window_cap(self, span: int) -> int:
        """`window_cap` of this cache's window and block size."""
        return window_cap(self.window, self.block_size, span)

    def reserve(self, n_blocks: int, owner: object = None, peak: int = 0,
                window: int = 0, window_peak: int = 0):
        """`n_blocks` blocks and, where the model keeps a state a
        sequence, a state slot, and, where it keeps window pools,
        `window` blocks of those: all or none. `peak` is the
        `peak_demand` of the admitted sequences with this one among
        them; the pool is short of blocks if it cannot hold that, however
        many are free now; `window_peak` likewise for the window pools.
        Returns (blocks, slot), slot None for a model without state
        (with window pools: (blocks, slot, window blocks)), or the name
        of what it was short of: "blocks", "state" or "window"."""
        if self.state_alloc is not None and not self.state_alloc.can_alloc(1):
            self.state_alloc.failed_allocs += 1
            return "state"
        alloc, walloc = self.allocator, self.window_alloc
        peak = max(int(peak), alloc.used + n_blocks)
        if peak > alloc.total:
            alloc.failed_allocs += 1
            return "blocks"
        if walloc is not None:
            window_peak = max(int(window_peak), walloc.used + window)
            if window_peak > walloc.total:
                walloc.failed_allocs += 1
                return "window"
            self.window_admit_peak = max(self.window_admit_peak, window_peak)
        self.admit_peak = max(self.admit_peak, peak)
        blocks = alloc.alloc(n_blocks, owner=owner)
        slot = None if self.state_alloc is None \
            else self.state_alloc.alloc(1, owner=owner)[0]
        if walloc is None:
            return blocks, slot
        return blocks, slot, walloc.alloc(window, owner=owner)

    def grow(self, table: List[int], owner: object = None,
             window: bool = False) -> None:
        """One more block at the end of a sequence's `table` (`window`:
        its window table, from the window pools), for the write position
        that has reached it. `reserve` admitted the sequence against the
        peak of such grants, so a refusal here is a fault of that
        account, not a full pool to wait out."""
        alloc = self.window_alloc if window else self.allocator
        got = alloc.alloc(1, owner=owner)
        if got is None:
            raise BackendError(
                f"paged pool: no {'window ' if window else ''}block left to "
                f"grow {owner!r} into, with {alloc.used} of {alloc.total} "
                f"live; admission by peak demand should have kept one free")
        table.extend(got)
        if window:
            self.window_blocks_grown += 1
        else:
            self.blocks_grown += 1

    def trim(self, table: List[int], pos: int, first: int = 0) -> int:
        """Give back the blocks of a sequence's window `table` that lie
        wholly behind the window of a query at `pos` (the lowest position
        any launch still to be dispatched will query): block b goes iff
        its last slot, (b + 1) * block_size - 1, is below pos - (window -
        1). Their entries read the scratch block from now on. Call it
        after the launch that last needed them was dispatched. `first`:
        the table's first entry not yet given back, as the call before
        returned it; returns the new one."""
        behind = min(max(int(pos) - (self.window - 1), 0) // self.block_size,
                     len(table))
        if behind <= first:
            return first
        self.window_alloc.free_blocks(table[first:behind])
        table[first:behind] = [SCRATCH_BLOCK] * (behind - first)
        self.window_blocks_freed += behind - first
        return behind

    def release(self, blocks: List[int], slot: Optional[int],
                window: Optional[List[int]] = None) -> None:
        """Give back what `reserve` and `grow` granted."""
        self.allocator.free_blocks(blocks)
        if slot is not None:
            self.state_alloc.free_blocks([slot])
        if window:
            self.window_alloc.free_blocks(
                [b for b in window if b != SCRATCH_BLOCK])

    @property
    def block_bytes(self) -> int:
        """Bytes one block holds over all layers and all pools that are
        paged under the one table (what a sequence holds by slot:
        `state_slot_bytes`; a window block: `window_block_bytes`)."""
        per_slot = ((1 if self.v is None else 2) * self.n_kv * self.head_dim
                    + self.idx_dim)
        return (self.n_layers * self.block_size * per_slot
                * self.dtype.itemsize)

    @property
    def window_block_bytes(self) -> int:
        """Bytes one block of the window pools holds, K and V over the
        window layers."""
        if self.wk is None:
            return 0
        return 2 * int(self.wk.nbytes) // self.wk.shape[1]

    @property
    def state_slot_bytes(self) -> int:
        """Bytes one slot holds: a sequence's state over all layers that
        keep one, and its rows (compressed keys, or convolutions' tails)."""
        return sum(int(p.nbytes) // p.shape[1]
                   for p in (self.state, self.slot_rows) if p is not None)

    def resident_bytes(self) -> int:
        return sum(int(p.nbytes) for p in self.pools())

    def stats(self) -> dict:
        out = self.allocator.stats()
        out["block_size"] = self.block_size
        out["tokens_capacity"] = self.tokens_capacity
        out["pools"] = len(self.pools())
        out["block_bytes"] = self.block_bytes
        out["blocks_grown"] = self.blocks_grown
        out["admit_peak_blocks"] = self.admit_peak
        out["blocks_live_high_water"] = self.allocator.high_water
        if self.state_alloc is not None:
            out["state_slots"] = self.state_alloc.total
            out["state_slots_used"] = self.state_alloc.used
            out["state_bytes"] = self.state_slot_bytes * (
                self.state_alloc.total + 1)
            out["state_slot_bytes"] = self.state_slot_bytes
            # of which the rows kept by slot (compressed keys or tails)
            out["slot_row_bytes"] = 0 if self.slot_rows is None else (
                int(self.slot_rows.nbytes) // self.slot_rows.shape[1])
        if self.window_alloc is not None:
            w = self.window_alloc
            out["window"] = {
                "blocks_total": w.total, "blocks_used": w.used,
                "blocks_live_high_water": w.high_water,
                "block_bytes": self.window_block_bytes,
                "admit_peak_blocks": self.window_admit_peak,
                "blocks_grown": self.window_blocks_grown,
                "failed_allocs": w.failed_allocs}
            out["window_blocks_freed"] = self.window_blocks_freed
        return out
