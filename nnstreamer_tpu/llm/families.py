"""One program set a model family: what `PagedLLMExecutor` serves, told
in one place.

`program_set` picks from `FAMILIES`, by `LMSpec.family` (a bundle with no
spec is `DENSE`), the object that answers everything the executor asks a
family, a kernel and a kind of call about:

- `program(kind)` for `prefill`, `chunk`, `decode` (and `ring` under
  shards): the function as it is, so that its device program keeps its
  name, with its static argument names and donated positions;
- `kernel(kind)` and `prefill_kind(params)`: which attention kernel
  serves a kind, and whether a whole prompt goes through the chunk;
- the call: `kw` / `chunk_kw` (static keywords), `prefill_args` /
  `chunk_args` / `decode_args` (positional layout), and `split`, which
  parts a result into logits, what the program returns beside them, and
  the pools. The executor carries what a chunk or a decode step returns
  beside as opaque device values, reads them back with the ids (or,
  after a chunk launched unsynced, once they are ready) and hands the
  host values to `note_beside`. What a whole-prompt program returns
  beside is dropped: a family that wants it read sends whole prompts
  through its chunk;
- what it refuses: at construction (`__init__`) and at submission
  (`check_prompt`);
- `idx_dim`: the width a token needs of the pool's blocks beyond K and V,
  and `cache_kw(n_layers)`: which layers keep K and V, the pools the
  family adds to `PagedKVCache` by slot (a fixed-size state and rows of
  compressed keys or of convolutions' tails a sequence, whose slot the
  executor hands `chunk_args` and `decode_args` as `slot` / `slots`), and
  the window layers' pools
  under a table a sequence of their own (handed as `window`, last);
  `values: False` where the family keeps no V pool, and `head_dim`, the
  width of a row of the `k` pool (a head's, or the latent family's one
  compressed row a token);
- its accounting: `note_decode`, `note_chunk` and `note_beside` count what
  a call attended and read and return what its span says of it; `stats()`
  is the family's part of the executor's.

A new family is a subclass and an entry of `FAMILIES`; the executor, the
engine and the element are not edited (docs/llm_serving.md).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Tuple

import numpy as np

from nnstreamer_tpu.core.errors import BackendError
from nnstreamer_tpu.llm.spec import (
    DELTA_MOE, DENSE, FULL, HYBRID, KDA, LATENT, LATENT_MOE, LINEAR, SPARSE,
    SPARSE_MOE, WINDOW, WINDOW_MOE)


def expert_tile_visits(counts: np.ndarray, tm: int) -> int:
    """The visits a call's grouped products make, one of its two
    products, over its expert layers: counts (layers, experts) are the
    pair rows each expert got, sorted by expert in each layer; an
    expert's rows span the row tiles of `tm` from its first row's to its
    last row's, and each is a visit (`pallas_ops.group_visits`, reckoned
    here on the host). An expert of no rows is not visited."""
    counts = counts.astype(np.int64)
    ends = np.cumsum(counts, axis=1)
    tiles = (ends - 1) // tm - (ends - counts) // tm + 1
    return int(tiles[counts > 0].sum())


def _note_experts(ps, counts: np.ndarray, bucket: int, kind: str) -> dict:
    """What a call's expert layers did (`experts.expert_layer`), for the
    set `ps` of a family that has them: counts (layers, held experts)
    are the pairs each expert got, and the rows the call was padded to
    give the pair rows and, by the rule, the row tile of its grouped
    products (`experts.expert_row_tile`, asked again here, on the host).
    Counts `EXPERT_COUNTERS` and returns the span's part: the distinct
    experts with a pair and the (row tile, expert) visits of one grouped
    product, over the layers (visits over experts touched is what the
    experts cost whose rows straddle a tile's edge and are read twice);
    a decode step's says the tile, a chunk's the pairs at the busiest
    expert and how full the visited tiles were."""
    from nnstreamer_tpu.llm.experts import expert_row_tile

    tm = expert_row_tile(bucket * ps.spec.experts_per_tok, ps.spec.n_experts)
    touched, visits = int((counts > 0).sum()), expert_tile_visits(counts, tm)
    c = ps.counters
    c["expert_tile_visits"] += visits
    c["expert_tile_rows"] += visits * tm
    said = {"experts_touched": touched, "expert_tile_visits": visits}
    if kind == "decode":
        c["expert_steps_layers"] += counts.shape[0]
        c["experts_touched_sum"] += touched
        return {**said, "expert_row_tile": tm}
    load_max = int(counts.max())
    c["expert_load_max_sum"] += load_max
    c["expert_load_chunks"] += 1
    return {**said, "expert_load_max": load_max, "expert_tile_fill_pct": round(
        100.0 * int(counts.sum()) / max(visits * tm, 1), 2)}


#: what `_note_experts` counts, tracer on or off. Decode steps: (layer,
#: step) pairs and the distinct experts that got a pair in them. Chunks
#: whose counts have been read back (`expert_load_chunks`): the pairs at
#: the busiest expert, largest over layers. Every call: the (row tile,
#: expert) visits one of its two grouped products made over its expert
#: layers, and the rows of those tiles
EXPERT_COUNTERS = ("expert_steps_layers", "experts_touched_sum",
                   "expert_load_max_sum", "expert_load_chunks",
                   "expert_tile_visits", "expert_tile_rows")


#: the three things a program of the causal tile update does with its
#: block of queries (`pallas_ops.block_reach`), as the counters name them
QBLOCK_KINDS = ("chunk_qblocks_clear", "chunk_qblocks_edge",
                "chunk_qblocks_skipped")


def _note_qblocks(ps, pos0: int, bucket: int, grp: int, tile: int,
                  walks) -> dict:
    """What the programs of `pallas_ops.causal_block_update` do over a
    chunk of `bucket` queries at `pos0`, `grp` query heads a KV head, for
    the set `ps`: `walks` are (layers, first tile, end tile, window), a
    kind of layer each, and every (block of queries, tile) pair of a walk
    is one program: the update with no mask (clear), under a mask made
    from positions (edge), or the carry handed through (skipped); the
    kernel's own arithmetic (`block_reach`, with its block,
    `causal_block_q`), here over all pairs at once. Counts them, summed
    over layers, and returns the span's part. No walk, where the update
    is the plain one: zeros."""
    from nnstreamer_tpu.backends.pallas_ops import block_reach, causal_block_q

    bq = causal_block_q(bucket, grp)
    q0 = pos0 + bq * np.arange(bucket // bq, dtype=np.int64)[:, None]
    clear = pairs = skipped = 0
    for layers, first, end, window in walks:
        s0 = tile * np.arange(int(first), int(end), dtype=np.int64)[None, :]
        skip, clr = block_reach(q0, bq, s0, tile, window)
        clear += layers * int(clr.sum())
        skipped += layers * int(skip.sum())
        pairs += layers * skip.size
    said = dict(zip(QBLOCK_KINDS, (clear, pairs - clear - skipped, skipped)))
    for name, n in said.items():
        ps.counters[name] += n
    return said


class Program(NamedTuple):
    fn: Callable
    static: Tuple[str, ...]
    donate: Tuple[int, ...]


class DenseSet:
    """The pre-norm rotary SwiGLU decoder (llm/paged_model.py), under
    either kernel (backends/pallas_paged.py) or sharded over `shards`
    chips, where `shard_fns()` gives the mesh-bound functions of
    `serving/sharding.make_llm_fns`."""

    family = DENSE
    idx_dim = 0

    def __init__(self, spec, *, name: str, params: dict, dtype,
                 n_heads: int, n_kv: int, head_dim: int, block_size: int,
                 max_blocks: int, kernel: str, shards: int = 0,
                 shard_fns: Callable[[], dict] = None, rows: int = 0,
                 chunk: int = 0):
        self.spec, self.name = spec, name
        #: the engine's rows and its prompt chunk (0: whole prompts), for
        #: a family whose pools are sized by them
        self.rows, self.chunk = int(rows), int(chunk)
        self.paged_kernel, self.shards = kernel, shards
        self._shard_fns = shard_fns
        self.n_kv, self.head_dim = n_kv, head_dim
        self.block_size, self.max_blocks = block_size, max_blocks
        #: the static arguments of this family's jits
        self.kw: Dict[str, Any] = {"n_heads": n_heads, "dtype": dtype}
        # decode attention's extent, kept tracer on or off: context
        # tokens the steps attended, and pool slots a layer read for
        # them (their ratio is the live share of what was read)
        self.counters: Dict[str, int] = {"kv_tokens_attended": 0,
                                         "kv_slots_read": 0}
        # only the XLA single-chip step walks live blocks; the Pallas
        # grid and the sharded step cover every table entry
        self._walks = not shards and kernel == "xla"

    # -- which program -----------------------------------------------------
    def kernel(self, kind: str) -> str:
        """Which attention kernel serves `kind`. The full-sequence
        prefill is always the XLA `apply_seq_kv` path (it is the bit-
        parity anchor against `transformer.generate`); chunk and decode
        follow the selected kernel."""
        return "xla" if kind == "prefill" else self.paged_kernel

    def prefill_kind(self, params: dict) -> str:
        """Whole-prompt prefills route through the chunk family (one
        chunk covering the prompt) when the selected kernel is Pallas or
        the bound params are W8A8-quantized — `apply_seq_kv` is float-
        only and kernel-fixed; the chunk path is quant-aware and
        kernel-selectable. Float + xla keeps the original path, so the
        token-for-token `generate` parity contract is untouched there."""
        if self.shards:
            # sharded init already refused pallas and quantized params;
            # the ring cutover is decided per prompt in prefill()
            return "prefill"
        if self.paged_kernel == "pallas":
            return "chunk"
        try:
            if "wqkv_scale" in params["blocks"][0]:
                return "chunk"
        except (KeyError, IndexError, TypeError):
            pass
        return "prefill"

    def program(self, kind: str) -> Program:
        static = ("n_heads", "dtype")
        if self.shards:
            if kind == "chunk":
                raise BackendError(
                    f"llm {self.name}: chunked prefill is not supported "
                    f"with shards={self.shards}; long prompts go through "
                    f"the sequence-parallel ring prefill "
                    f"(ring_prefill_min)")
            # one SPMD executable per bucket under ("tp", N, version) —
            # same donate/static discipline as the single-chip jits
            return Program(self._shard_fns()[kind], static, (4, 5))
        from nnstreamer_tpu.llm import paged_model as xla

        if kind == "prefill":
            return Program(xla.paged_prefill, static, (4, 5))
        if self.paged_kernel == "pallas":
            from nnstreamer_tpu.backends import pallas_paged as pallas

            if kind == "chunk":
                return Program(pallas.paged_flash_prefill_chunk, static,
                               (6, 7))
            return Program(pallas.paged_flash_decode_step, static, (4, 5))
        if kind == "chunk":
            return Program(xla.paged_prefill_chunk, static, (6, 7))
        return Program(xla.paged_decode_step, static, (4, 5))

    # -- the call ----------------------------------------------------------
    def chunk_kw(self, pos0: int, bucket: int) -> dict:
        return self.kw

    def prefill_args(self, params, ids, blk_idx, blk_off, last,
                     pools) -> tuple:
        return (params, ids, blk_idx, blk_off, *pools, last)

    def chunk_args(self, params, ids, pos0, blk_idx, blk_off, tab, last,
                   pools, slot=None, window=None) -> tuple:
        return (params, ids, pos0, blk_idx, blk_off, tab, *pools, last)

    def decode_args(self, params, cur, tab, pos, n: int, pools,
                    slots=None, window=None) -> tuple:
        return (params, cur, tab, pos, *pools)

    def cache_kw(self, n_layers: int) -> dict:
        """What `PagedKVCache` is built with beyond the pool's geometry:
        every layer keeps K and V, and there is no other state."""
        return {"n_layers": n_layers, "n_kv": self.n_kv}

    #: the device values a chunk or a decode step returns beside its logits
    beside = 0

    def split(self, out: tuple) -> tuple:
        """A program's result as (logits, the device values it returns
        beside them, the pools)."""
        logits, *rest = out
        return logits, tuple(rest[:self.beside]), rest[self.beside:]

    # -- what it refuses at submission -------------------------------------
    def check_prompt(self, plen: int, prefill_chunk: int) -> None:
        """Refuse a prompt this family can never prefill."""

    # -- accounting --------------------------------------------------------
    def note_decode(self, pos_a: np.ndarray, n: int) -> dict:
        """Count what one decode step from the bucket's positions
        `pos_a` (`n` live rows first) attends and reads, and return its
        span's part: kv_tokens, the live rows' context with the step's
        own tokens; kv_slots, the pool slots one layer gathers, padding
        rows and the walk's rounding included."""
        if self._walks:
            from nnstreamer_tpu.llm import parts

            nb_c, _, t = parts.walk_plan(self.block_size, self.n_kv,
                                         self.head_dim, len(pos_a),
                                         self.max_blocks)
            slots = parts.walk_slots(pos_a, self.block_size, nb_c, t)
        else:
            slots = len(pos_a) * self.max_blocks * self.block_size
        tokens = int(pos_a[:n].sum()) + n
        self.counters["kv_tokens_attended"] += tokens
        self.counters["kv_slots_read"] += slots
        return {"kv_tokens": tokens, "kv_slots": slots}

    def note_chunk(self, pos0: int, clen: int, bucket: int) -> dict:
        """Count what a chunk of `clen` tokens at `pos0`, padded to
        `bucket`, reads that the host can tell from those three, and
        return its span's part."""
        return {}

    def note_beside(self, kind: str, host: list, bucket: int = 0) -> dict:
        """Account what a `chunk` or a `decode` returned beside its
        logits, now on the host, and return its span's part. `bucket`:
        the rows the call was padded to."""
        return {}

    def stats(self) -> dict:
        return dict(self.counters)


class ChunkOnlySet(DenseSet):
    """What the families share that have one prefill program, their
    chunk: whole prompts go through it, up to a length."""

    #: the longest prompt the one-chunk whole-prompt prefill takes: past
    #: it a chunk's temporaries outgrow what the pool leaves free, and
    #: the engine has to chunk (prefill_chunk)
    WHOLE_PROMPT_MAX = 4096

    #: what none of them is served with yet, why in the family's own words
    NO_SHARDS = NO_W8A8 = ""
    NO_PALLAS = "it has no Pallas twin yet"

    def __init__(self, spec, *, params: dict, **given):
        super().__init__(spec, params=params, **given)
        if self.shards > 0:
            why = f"shards={self.shards}: {self.NO_SHARDS}"
        elif self.paged_kernel == "pallas":
            why = (f"paged_kernel=pallas: {self.NO_PALLAS}; set "
                   f"paged_kernel=xla")
        elif any(k.endswith("_scale") for b in params["blocks"] for k in b):
            why = f"a W8A8 store version: {self.NO_W8A8}"
        else:
            why = self.refusal(params)
        if why is not None:
            raise BackendError(
                f"llm {self.name}: the {self.family} family cannot be "
                f"served with {why}")
        self.kw = {"spec": spec, "dtype": self.kw["dtype"]}

    def refusal(self, params: dict):
        """What of the spec, the bundle or the pool's geometry the family
        cannot serve, in words, or None: the checks that are its own."""
        return None

    def prefill_kind(self, params: dict) -> str:
        return "chunk"

    def _fused(self, bucket: int) -> bool:
        """Whether the chunk's attention walk updates a context tile in
        one kernel (`parts.fused_attend`)."""
        from nnstreamer_tpu.llm import parts

        return parts.fused_attend(bucket, parts.CTX_TILE, self.head_dim)

    def chunk_kw(self, pos0: int, bucket: int) -> dict:
        """Whole blocks are written at once where the chunk lies on
        them: every chunk of a prompt does when block_size divides
        prefill_chunk, so the bucket stays one program."""
        from nnstreamer_tpu.llm.parts import CTX_TILE

        bs = self.block_size
        kw = dict(self.kw, fused=self._fused(bucket),
                  by_block=int(pos0) % bs == 0 and bucket % bs == 0)
        # the walk's tile, for a program that takes it as a static argument
        if "tile" in self.program("chunk").static:
            kw["tile"] = CTX_TILE
        return kw

    def decode_args(self, params, cur, tab, pos, n: int, pools,
                    slots=None, window=None) -> tuple:
        # n live rows: a step's padding rows reach no expert
        return (params, cur, tab, pos, np.int32(n), *pools)

    def tiles(self, pos0: int, bucket: int, window: int = 0):
        """(first, end) of the context tiles a chunk's walk covers: the
        program's own trip count (`parts.tile_span`), on the host."""
        from nnstreamer_tpu.llm import parts

        return parts.tile_span(pos0, bucket,
                               self.max_blocks * self.block_size,
                               parts.CTX_TILE, window)

    def check_prompt(self, plen: int, prefill_chunk: int) -> None:
        """The family prefills through its chunk program only, and one
        chunk holds at most WHOLE_PROMPT_MAX."""
        if plen > self.WHOLE_PROMPT_MAX and not 0 < prefill_chunk < plen:
            raise BackendError(
                f"llm {self.name}: a prompt of {plen} tokens needs "
                f"chunked prefill in the {self.family} family (one chunk "
                f"holds at most {self.WHOLE_PROMPT_MAX}); set "
                f"prefill_chunk (it is {prefill_chunk})")

    def stats(self) -> dict:
        return dict(self.counters, family=self.family)


class SparseMoESet(ChunkOnlySet):
    """The sparse-expert decoder whose attention a learned indexer
    chooses (llm/sparse_moe.py): one prefill program, its chunk; a third
    pool of indexer keys; each call returns the tokens an expert got,
    (layers, experts), beside its logits."""

    family = SPARSE_MOE
    beside = 1

    NO_SHARDS = "its experts and indexer pool have no sharding rule yet"
    NO_W8A8 = "its grouped expert products are float only"

    def __init__(self, spec, *, params: dict, **given):
        super().__init__(spec, params=params, **given)
        self.idx_dim = int(spec.idx_dim)
        # kept tracer on or off, beside `EXPERT_COUNTERS`. Decode steps:
        # context slots the indexer scored / slots selected and attended
        # / indexer-pool slots a layer read (kv_slots_read then counts
        # the selected slots' gathers). Every call: (token, expert) pairs
        # routed. Chunks: context tiles a layer's walks covered, and
        # those of them the attention walk updated in one kernel
        # (`parts.fused_attend`).
        self.counters.update(dict.fromkeys((
            "kv_tokens_scored", "kv_tokens_selected", "idx_slots_read",
            "expert_tokens", "chunk_tiles_attended", "chunk_tiles_fused")
            + EXPERT_COUNTERS, 0))

    def program(self, kind: str) -> Program:
        from nnstreamer_tpu.llm import sparse_moe

        if kind == "chunk":
            return Program(sparse_moe.sparse_moe_prefill_chunk,
                           ("spec", "dtype", "by_block", "fused"),
                           (6, 7, 8))
        return Program(sparse_moe.sparse_moe_decode_step,
                       ("spec", "dtype"), (5, 6, 7))

    def note_decode(self, pos_a: np.ndarray, n: int) -> dict:
        """The indexer scores each live row's context (kv_tokens_scored)
        reading the bucket's whole tables of the indexer pool
        (idx_slots_read); the step attends min(topk, pos + 1) slots a
        row (kv_tokens_selected, also kv_tokens_attended) and gathers
        `topk` slots of K and V for every row of the bucket
        (kv_slots_read)."""
        s_max = self.max_blocks * self.block_size
        k = min(int(self.spec.topk), s_max)
        scored = int(pos_a[:n].sum()) + n
        selected = int(np.minimum(k, pos_a[:n] + 1).sum())
        slots, idx_slots = len(pos_a) * k, len(pos_a) * s_max
        c = self.counters
        c["kv_tokens_scored"] += scored
        c["kv_tokens_selected"] += selected
        c["kv_tokens_attended"] += selected
        c["idx_slots_read"] += idx_slots
        c["kv_slots_read"] += slots
        return {"kv_tokens": scored, "kv_slots": slots,
                "kv_selected": selected, "idx_slots": idx_slots}

    def note_chunk(self, pos0: int, clen: int, bucket: int) -> dict:
        """The context tiles each of a layer's three walks covers (the
        program's own count, `parts.tile_span`), and which update the
        attention walk made them with."""
        _, tiles = self.tiles(pos0, bucket)
        fused = self._fused(bucket)
        self.counters["chunk_tiles_attended"] += tiles
        self.counters["chunk_tiles_fused"] += tiles * fused
        return {"attend": "fused" if fused else "plain",
                "ctx_tiles": tiles}

    def note_beside(self, kind: str, host: list, bucket: int = 0) -> dict:
        """One call's (layers, experts) token counts: the pairs routed,
        and what the expert layers did with them (`_note_experts`)."""
        counts, = host
        self.counters["expert_tokens"] += int(counts.sum())
        return _note_experts(self, counts, bucket, kind)


class HybridSet(ChunkOnlySet):
    """The decoder whose layers are linear attention with a carried
    state or block-sparse attention over paged KV (llm/hybrid_lm.py):
    one prefill program, its chunk; K and V for the sparse layers only,
    and a slot a sequence for the linear layers' states and the sparse
    layers' compressed keys."""

    family = HYBRID

    NO_SHARDS = ("its state and compressed-key pools have no sharding rule "
                 "yet")
    NO_W8A8 = "its state update and gathered attention are float only"

    def refusal(self, params: dict):
        spec = self.spec
        if self.block_size != spec.ck_stride:
            return (f"block_size={self.block_size}: a sequence keeps one "
                    f"compressed key a block of its table, so block_size "
                    f"has to be the keys' stride, {spec.ck_stride}")
        if len(spec.layer_kinds) != len(params["blocks"]):
            return (f"a bundle of {len(params['blocks'])} layers under a "
                    f"spec that names {len(spec.layer_kinds)}")
        return None

    def __init__(self, spec, *, params: dict, **given):
        super().__init__(spec, params=params, **given)
        self.n_linear = spec.layer_kinds.count(LINEAR)
        self.n_sparse = spec.layer_kinds.count(SPARSE)
        #: bytes of one sequence's state, all linear layers
        self.state_bytes = (self.n_linear * spec.lin_heads
                            * spec.head_dim * spec.head_dim * 4)
        # kept tracer on or off; one sparse layer's worth each, a KV
        # head's where heads select apart. Decode steps and chunks:
        # state bytes read and written (all linear layers), compressed
        # keys scored, selection blocks and tokens attended, pool slots
        # gathered for them (padding rows and whole blocks included).
        # Chunks: the context tiles a sparse layer's walk covered
        self.counters.update(dict.fromkeys((
            "state_bytes_rw", "ckeys_scored", "kv_blocks_selected",
            "kv_tokens_selected", "chunk_tiles_attended"), 0))

    def cache_kw(self, n_layers: int) -> dict:
        # K and V of the sparse layers only, a KV head a pool layer
        # (llm/hybrid_lm.py says why); by slot, the linear layers' states
        # and a compressed key a block of the longest table
        s = self.spec
        heads = self.n_sparse * self.n_kv
        return {"n_layers": heads, "n_kv": 1,
                "state_shape": (self.n_linear, s.lin_heads, s.head_dim,
                                s.head_dim),
                "row_shape": (heads, self.max_blocks, s.head_dim)}

    def program(self, kind: str) -> Program:
        from nnstreamer_tpu.llm import hybrid_lm

        if kind == "chunk":
            return Program(hybrid_lm.hybrid_prefill_chunk,
                           ("spec", "dtype", "by_block", "fused", "tile"),
                           (7, 8, 9, 10))
        return Program(hybrid_lm.hybrid_decode_step, ("spec", "dtype"),
                       (5, 6, 7, 8))

    def chunk_args(self, params, ids, pos0, blk_idx, blk_off, tab, last,
                   pools, slot=None, window=None) -> tuple:
        return (params, ids, pos0, blk_idx, blk_off, tab, slot, *pools,
                last)

    def decode_args(self, params, cur, tab, pos, n: int, pools,
                    slots=None, window=None) -> tuple:
        return (params, cur, tab, pos, slots, *pools)

    def _count(self, said: dict) -> dict:
        for counter, key in (("ckeys_scored", "ckeys_scored"),
                             ("kv_blocks_selected", "blocks_selected"),
                             ("kv_tokens_selected", "kv_selected"),
                             ("kv_tokens_attended", "kv_selected"),
                             ("kv_slots_read", "kv_slots")):
            self.counters[counter] += said[key]
        return said

    def note_decode(self, pos_a: np.ndarray, n: int) -> dict:
        """Each live row's state is read and written once a linear
        layer (state_rows, state_bytes_rw); in a sparse layer it scores
        the compressed keys complete by its position, selects blocks and
        attends their tokens up to its own; every row of the bucket
        gathers sel_topk blocks (`hybrid_lm.sparse_attend_rows`)."""
        s = self.spec
        self.counters["state_bytes_rw"] += 2 * n * self.state_bytes
        slots = len(pos_a) * min(s.sel_topk * s.sel_block,
                                 self.max_blocks * self.block_size)
        return {"state_rows": n, "kv_tokens": int(pos_a[:n].sum()) + n,
                **self._count(_sparse_reads(
                    s, pos_a[:n].astype(np.int64), slots))}

    def note_chunk(self, pos0: int, clen: int, bucket: int) -> dict:
        """The context tiles a sparse layer's walk covers (the program's
        own trip count, `parts.tile_span` of the tile `chunk_kw` hands
        it) and the slots it gathers for them, a KV head."""
        from nnstreamer_tpu.llm.parts import CTX_TILE

        _, tiles = self.tiles(pos0, bucket)
        self.counters["state_bytes_rw"] += 2 * self.state_bytes
        self.counters["chunk_tiles_attended"] += tiles
        return {"pos0": pos0, "state_rows": 1, "ctx_tiles": tiles,
                **self._count(_chunk_reads(self.spec, pos0, clen,
                                           tiles * CTX_TILE))}


class HeldExpertsSet(ChunkOnlySet):
    """What the families share whose expert layers hold a share of the
    routed experts: each call returns, an expert layer, the tokens each
    held expert got and the pairs routed to experts that are not held,
    beside its logits."""

    beside = 1

    def __init__(self, spec, *, params: dict, **given):
        super().__init__(spec, params=params, **given)
        # kept tracer on or off, beside `EXPERT_COUNTERS` (of the held
        # experts). Every call: (token, expert) pairs of real tokens
        # routed to held experts and away. Chunks: the programs of the
        # fused tile update, (block of queries, tile) pairs over all
        # layers, by what `pallas_ops.block_reach` has each do
        self.counters.update(dict.fromkeys((
            "expert_pairs_held", "expert_pairs_away")
            + EXPERT_COUNTERS + QBLOCK_KINDS, 0))

    def note_beside(self, kind: str, host: list, bucket: int = 0) -> dict:
        """One call's (expert layers, held + 1) counts: the real tokens'
        pairs at held experts and away, and what the expert layers did
        with those held (`_note_experts`)."""
        load, = host
        counts, away = load[:, :-1], int(load[:, -1].sum())
        held = int(counts.sum())
        self.counters["expert_pairs_held"] += held
        self.counters["expert_pairs_away"] += away
        return {**_note_experts(self, counts, bucket, kind),
                "expert_pairs_held": held, "expert_pairs_away": away}


class WindowMoESet(HeldExpertsSet):
    """The decoder whose layers attend a window of the newest positions
    or the whole context (llm/window_moe.py): one prefill program, its
    chunk; a pair of K and V pools a layer kind, each under a table a
    sequence of its own (`window`: the window layers' table, or for a
    chunk its write targets and table); each call returns, an expert
    layer, the tokens each held expert got and the pairs routed to
    experts that are not held, beside its logits."""

    family = WINDOW_MOE

    NO_SHARDS = ("its two pairs of pools and its share of the experts have "
                 "no sharding rule yet")
    NO_PALLAS = "it has no windowed Pallas twin yet"
    NO_W8A8 = "its grouped expert products are float only"

    def refusal(self, params: dict):
        spec, kinds = self.spec, self.spec.layer_kinds
        if len(kinds) != len(params["blocks"]):
            return (f"a bundle of {len(params['blocks'])} layers under a "
                    f"spec that names {len(kinds)}")
        if set(kinds) != {WINDOW, FULL} or spec.window < 1:
            return (f"layer kinds {sorted(set(kinds))} and a window of "
                    f"{spec.window}: it serves layers of both kinds, "
                    f"'{WINDOW}' and '{FULL}', and a window >= 1")
        if not 0 <= spec.dense_layers < len(kinds):
            return (f"dense_layers={spec.dense_layers} of {len(kinds)}: at "
                    f"least one layer has to be an expert layer")
        return None

    def __init__(self, spec, *, params: dict, **given):
        super().__init__(spec, params=params, **given)
        kinds = spec.layer_kinds
        self.n_window, self.n_full = kinds.count(WINDOW), kinds.count(FULL)
        self.held = spec.experts_held or spec.n_experts
        # kept tracer on or off. Decode steps: live context a FULL layer
        # and a WINDOW layer attended, pool slots all layers gathered for
        # it (whole iterations, padding rows included; a slot is n_kv x
        # head_dim values, K and V). Chunks: context tiles a FULL and a
        # WINDOW layer's walk covered
        self.counters.update(dict.fromkeys((
            "kv_tokens_full", "kv_tokens_window", "ctx_tiles_full",
            "ctx_tiles_window"), 0))

    def cache_kw(self, n_layers: int) -> dict:
        """The FULL layers' pools under the pool's geometry as given;
        the WINDOW layers' sized by the rows, not by the context: every
        row at its decode cap, the one prompt whose chunk is computed at
        its chunk's cap, and the scratch block."""
        from nnstreamer_tpu.llm.paged_cache import window_cap

        w, bs = self.spec.window, self.block_size
        span = min(self.chunk or self.WHOLE_PROMPT_MAX,
                   self.max_blocks * bs)
        blocks = (max(self.rows, 1) * window_cap(w, bs, 1)
                  + window_cap(w, bs, span) - window_cap(w, bs, 1) + 1)
        return {"n_layers": self.n_full, "n_kv": self.n_kv,
                "window_layers": self.n_window, "window": w,
                "window_blocks": blocks}

    def program(self, kind: str) -> Program:
        from nnstreamer_tpu.llm import window_moe

        if kind == "chunk":
            return Program(window_moe.window_moe_prefill_chunk,
                           ("spec", "dtype", "by_block", "fused", "tile"),
                           (8, 9, 10, 11))
        return Program(window_moe.window_moe_decode_step,
                       ("spec", "dtype"), (6, 7, 8, 9))

    def chunk_args(self, params, ids, pos0, blk_idx, blk_off, tab, last,
                   pools, slot=None, window=None) -> tuple:
        wblk_idx, wtab = window
        return (params, ids, pos0, blk_idx, blk_off, tab, wblk_idx, wtab,
                *pools, last)

    def decode_args(self, params, cur, tab, pos, n: int, pools,
                    slots=None, window=None) -> tuple:
        # n live rows: a step's padding rows reach no expert
        return (params, cur, tab, window, pos, np.int32(n), *pools)

    def note_decode(self, pos_a: np.ndarray, n: int) -> dict:
        """A FULL layer attends each live row's whole context, a WINDOW
        layer its newest `window` positions; each kind's work list is
        walked in whole iterations of T chunks of C slots
        (`parts.walk_plan`), every row of the bucket in it."""
        from nnstreamer_tpu.llm.parts import walk_plan

        nb_c, _, t = walk_plan(self.block_size, self.n_kv, self.head_dim,
                               len(pos_a), self.max_blocks)
        c = nb_c * self.block_size
        pos = pos_a.astype(np.int64)
        lo = np.maximum(pos - (self.spec.window - 1), 0)

        def slots(items: int) -> int:
            return -(-items // t) * t * c

        said = {"kv_tokens_full": int(pos[:n].sum()) + n,
                "kv_tokens_window": int((pos[:n] - lo[:n]).sum()) + n,
                "kv_slots": self.n_full * slots(int((pos // c + 1).sum()))
                + self.n_window * slots(int((pos // c - lo // c + 1).sum()))}
        count = self.counters
        count["kv_tokens_full"] += said["kv_tokens_full"]
        count["kv_tokens_window"] += said["kv_tokens_window"]
        count["kv_tokens_attended"] += (
            self.n_full * said["kv_tokens_full"]
            + self.n_window * said["kv_tokens_window"])
        count["kv_slots_read"] += said["kv_slots"]
        return said

    def note_chunk(self, pos0: int, clen: int, bucket: int) -> dict:
        """The context tiles a FULL and a WINDOW layer's walk covers (the
        program's own trip counts, `parts.tile_span`) and, where the tile
        update is the fused one, what its programs do."""
        from nnstreamer_tpu.llm.parts import CTX_TILE

        spec, fused = self.spec, self._fused(bucket)
        _, full = self.tiles(pos0, bucket)
        first, end = self.tiles(pos0, bucket, spec.window)
        self.counters["ctx_tiles_full"] += full
        self.counters["ctx_tiles_window"] += end - first
        walks = ((self.n_full, 0, full, 0),
                 (self.n_window, first, end, spec.window))
        return {"pos0": pos0, "attend": "fused" if fused else "plain",
                "ctx_tiles_full": int(full),
                "ctx_tiles_window": int(end - first),
                **_note_qblocks(self, pos0, bucket, spec.n_heads // spec.n_kv,
                                CTX_TILE, walks if fused else ())}


class LatentMoESet(HeldExpertsSet):
    """The decoder whose attention is latent (llm/latent_moe.py): one
    prefill program, its chunk; two pools under one table, a token's
    compressed row (`k`: one row of `kv_rank` a slot, no head axis) and
    its roped key (`idx`, packed as the indexer's keys), and no V pool;
    the decode step attends in the latent (absorbed), a chunk in the form
    `latent_moe.expanded_attend` picks from its bucket; each call
    returns, an expert layer, the tokens each held expert got and the
    pairs routed to experts that are not held, beside its logits."""

    family = LATENT_MOE

    NO_SHARDS = ("`kv_pool_placer` shards the pools along their head axis, "
                 "and a latent row has none")
    NO_PALLAS = ("that names the dense family's twin programs; this family's "
                 "decode walk takes its kernel from the backend and the "
                 "pools' widths alone (`latent_moe.fused_decode`)")
    NO_W8A8 = ("its absorbed products and its grouped expert products are "
               "float only")

    def refusal(self, params: dict):
        spec, layers = self.spec, len(params["blocks"])
        if min(spec.kv_rank, spec.nope_dim, spec.v_dim) < 1 \
                or spec.q_rank < 0 or spec.rope_dim < 2 or spec.rope_dim % 2:
            return (f"ranks {spec.q_rank} / {spec.kv_rank} and head widths "
                    f"{spec.nope_dim} + {spec.rope_dim} / {spec.v_dim}: "
                    f"every one has to be set (a q_rank of 0 is a query "
                    f"with no low-rank step), the roped width even")
        # a query with no rank goes through one matrix, `wq`, where a
        # ranked one goes through `wqa`, a norm and `wqb`
        need = ("wqa", "q_norm", "wqb") if spec.q_rank else ("wq",)
        short = sorted({k for b in self._latent_blocks(params)
                        for k in need if k not in b})
        if short:
            return (f"q_rank={spec.q_rank}: a latent layer's query needs "
                    f"{' and '.join(need)} (D -> "
                    f"{'q_rank -> ' if spec.q_rank else ''}heads x (nope_dim "
                    f"+ rope_dim)), and the bundle has no {', '.join(short)}")
        if not 0 <= spec.dense_layers < layers:
            return (f"dense_layers={spec.dense_layers} of {layers}: at "
                    f"least one layer has to be an expert layer")
        if spec.n_group > 1 and (
                spec.n_experts % spec.n_group
                or not 0 < spec.topk_group <= spec.n_group
                or spec.experts_per_tok > spec.topk_group
                * (spec.n_experts // spec.n_group)):
            return (f"{spec.n_experts} experts in {spec.n_group} groups of "
                    f"which {spec.topk_group} are chosen for "
                    f"{spec.experts_per_tok} a token: the groups have to "
                    f"be equal and the chosen ones hold a token's experts")
        return None

    def _latent_blocks(self, params: dict) -> list:
        """The layers that attend in the latent: all of this family's."""
        return params["blocks"]

    def __init__(self, spec, *, params: dict, **given):
        super().__init__(spec, params=params, **given)
        # the pool's row: one latent a token and, beside it, its roped key
        self.n_kv, self.head_dim = 1, int(spec.kv_rank)
        self.idx_dim = int(spec.rope_dim)
        #: the layers that keep a row of the pool a token
        self.layers = len(self._latent_blocks(params))
        self.expert_layers = len(params["blocks"]) - spec.dense_layers
        # kept tracer on or off. Decode steps: live context the steps
        # attended, pool slots a layer read for it (`note_decode` says
        # which under each walk; a slot is kv_rank + rope_dim values), the
        # steps by their walk (`latent_moe.fused_decode`). Chunks: context
        # tiles a layer's walk covered, and the context tokens a layer
        # put through Wkvb (the expanded form's; 0 for an absorbed chunk)
        self.counters.update(dict.fromkeys((
            "decode_steps_fused", "decode_steps_plain", "latents_expanded",
            "chunk_tiles_attended"), 0))

    def cache_kw(self, n_layers: int) -> dict:
        return {"n_layers": n_layers, "n_kv": 1, "values": False}

    def program(self, kind: str) -> Program:
        from nnstreamer_tpu.llm import latent_moe

        if kind == "chunk":
            return Program(latent_moe.latent_moe_prefill_chunk,
                           ("spec", "dtype", "by_block", "fused",
                            "expanded", "tile"), (6, 7))
        return Program(latent_moe.latent_moe_decode_step,
                       ("spec", "dtype"), (5, 6))

    def _expanded(self, bucket: int) -> bool:
        from nnstreamer_tpu.llm.latent_moe import expanded_attend

        return expanded_attend(bucket, self.spec)

    def _fused(self, bucket: int) -> bool:
        """The expanded form's tile update in one kernel, where a head's
        values are whole lane tiles (its keys are filled up to that)."""
        from nnstreamer_tpu.llm import parts

        return self._expanded(bucket) and parts.fused_attend(
            bucket, parts.CTX_TILE, self.spec.v_dim)

    def chunk_kw(self, pos0: int, bucket: int) -> dict:
        return dict(super().chunk_kw(pos0, bucket),
                    expanded=self._expanded(bucket))

    def note_decode(self, pos_a: np.ndarray, n: int) -> dict:
        """Every layer attends each live row's whole context in the
        latent (kv_tokens). kv_slots, what a layer reads of the pool for
        it: under the fused walk the live rows' blocks up to whole steps
        of the kernel, which copies nothing for a padding row
        (`latent_moe.fused_slots`); under the plain walk whole iterations
        of T chunks of C slots, every row of the bucket in them
        (`parts.walk_slots` under `latent_moe.walk_plan`). `attend` says
        which, by the program's own rule."""
        from nnstreamer_tpu.llm import latent_moe, parts

        fused = latent_moe.fused_decode(self.block_size, self.spec,
                                        self.kw["dtype"])
        if fused:
            slots = latent_moe.fused_slots(pos_a, n)
        else:
            nb_c, _, t = latent_moe.walk_plan(self.block_size, len(pos_a),
                                              self.max_blocks)
            slots = parts.walk_slots(pos_a, self.block_size, nb_c, t)
        tokens = int(pos_a[:n].sum()) + n
        self.counters["kv_tokens_attended"] += tokens
        self.counters["kv_slots_read"] += slots
        self.counters["decode_steps_fused" if fused
                      else "decode_steps_plain"] += 1
        return {"kv_tokens": tokens, "kv_slots": slots,
                "attend": "fused" if fused else "plain"}

    def note_chunk(self, pos0: int, clen: int, bucket: int) -> dict:
        """The context tiles a layer's walk covers (the program's own
        trip count, `parts.tile_span`), the form it attends them in,
        expanded, the context tokens a layer puts through Wkvb and, where
        the tile update is the fused one (a head a group of one), what
        its programs do."""
        from nnstreamer_tpu.llm.parts import CTX_TILE

        _, tiles = self.tiles(pos0, bucket)
        expanded = self._expanded(bucket)
        through = int(tiles) * CTX_TILE * expanded
        self.counters["chunk_tiles_attended"] += int(tiles)
        self.counters["latents_expanded"] += through
        walks = ((self.layers, 0, tiles, 0),) if self._fused(bucket) else ()
        return {"pos0": pos0, "ctx_tiles": int(tiles),
                "attend": "expanded" if expanded else "absorbed",
                "latents_expanded": through,
                **_note_qblocks(self, pos0, bucket, 1, CTX_TILE, walks)}


class DeltaMoESet(LatentMoESet):
    """The decoder whose layers are gated delta-rule linear attention or
    latent attention (llm/delta_moe.py): one prefill program, its chunk;
    two kinds of cache in one model: by block, the latent layers' two
    pools (`LatentMoESet`'s, read by its layer programs in its two
    forms); by slot, a float32 state a KDA layer and the tails of its
    three convolutions; each call returns, an expert layer, the tokens
    each held expert got and the pairs routed to experts that are not
    held, beside its logits."""

    family = DELTA_MOE

    NO_SHARDS = ("a sequence's state and tails live by slot on one chip and "
                 "the latent pool has no head axis to shard along: neither "
                 "has a sharding rule yet")
    NO_PALLAS = ("that names the dense family's twin programs; the delta "
                 "rule's decode update and the latent layers' decode walk "
                 "take their kernels from the backend and the widths alone "
                 "(`delta_moe.fused_state`, `latent_moe.fused_decode`)")
    NO_W8A8 = ("the delta rule's state and the products that feed it are "
               "float32, and its grouped expert products are float only")

    def _latent_blocks(self, params: dict) -> list:
        return [b for b, kind in zip(params["blocks"], self.spec.layer_kinds)
                if kind == LATENT]

    def refusal(self, params: dict):
        spec, kinds = self.spec, self.spec.layer_kinds
        if len(kinds) != len(params["blocks"]):
            return (f"a bundle of {len(params['blocks'])} layers under a "
                    f"spec that names {len(kinds)}")
        if set(kinds) != {KDA, LATENT}:
            return (f"layer kinds {sorted(set(kinds))}: it serves layers of "
                    f"both kinds, '{KDA}' and '{LATENT}'")
        if min(spec.lin_heads, spec.head_dim) < 1 or spec.conv_kernel < 2:
            return (f"{spec.lin_heads} KDA heads of {spec.head_dim} and a "
                    f"convolution over {spec.conv_kernel} tokens: every one "
                    f"has to be set, the convolution over at least 2 (it "
                    f"carries a tail)")
        return super().refusal(params)

    def __init__(self, spec, *, params: dict, **given):
        super().__init__(spec, params=params, **given)
        self.n_kda = spec.layer_kinds.count(KDA)
        width = 3 * spec.lin_heads * spec.head_dim
        #: bytes of one sequence's state, and of its tails, all KDA layers
        self.state_bytes = (self.n_kda * spec.lin_heads * spec.head_dim
                            * spec.head_dim * 4)
        self.tail_bytes = (self.n_kda * (spec.conv_kernel - 1) * width
                           * np.dtype(self.kw["dtype"]).itemsize)
        # kept tracer on or off, beside the latent set's. Decode steps and
        # chunks: state and tail bytes read and written (all KDA layers),
        # the rows whose state a call advanced; decode steps by how their
        # states moved (`delta_moe.fused_state`). Chunks: those that started
        # from zero, and the runs of the closed form a KDA layer took
        self.counters.update(dict.fromkeys((
            "state_rows", "state_bytes_rw", "tail_bytes_rw",
            "state_steps_fused", "state_steps_gathered", "chunks_fresh",
            "delta_runs"), 0))

    def cache_kw(self, n_layers: int) -> dict:
        # by block, the latent layers' rows alone; by slot, the KDA
        # layers' states and the last inputs of their convolutions,
        s = self.spec
        return {"n_layers": self.layers, "n_kv": 1, "values": False,
                "state_shape": (self.n_kda, s.lin_heads, s.head_dim,
                                s.head_dim),
                # one row a slot: the K - 1 inputs side by side
                "row_shape": (self.n_kda, 1, (s.conv_kernel - 1)
                              * 3 * s.lin_heads * s.head_dim)}

    def program(self, kind: str) -> Program:
        from nnstreamer_tpu.llm import delta_moe

        if kind == "chunk":
            return Program(delta_moe.delta_moe_prefill_chunk,
                           ("spec", "dtype", "by_block", "fused",
                            "expanded", "tile", "run"), (7, 8, 9, 10))
        return Program(delta_moe.delta_moe_decode_step,
                       ("spec", "dtype"), (6, 7, 8, 9))

    def chunk_args(self, params, ids, pos0, blk_idx, blk_off, tab, last,
                   pools, slot=None, window=None) -> tuple:
        return (params, ids, pos0, blk_idx, blk_off, tab, slot, *pools,
                last)

    def decode_args(self, params, cur, tab, pos, n: int, pools,
                    slots=None, window=None) -> tuple:
        return (params, cur, tab, pos, np.int32(n), slots, *pools)

    def chunk_kw(self, pos0: int, bucket: int) -> dict:
        # the closed form's run, as the walk's tile: a static argument
        from nnstreamer_tpu.llm.delta_moe import RUN

        return dict(super().chunk_kw(pos0, bucket), run=RUN)

    def _note_state(self, rows: int) -> dict:
        said = {"state_rows": rows,
                "state_bytes_rw": 2 * rows * self.state_bytes,
                "tail_bytes_rw": 2 * rows * self.tail_bytes}
        for name, n in said.items():
            self.counters[name] += n
        return said

    def note_decode(self, pos_a: np.ndarray, n: int) -> dict:
        """Each live row's state and tails are read and written once a
        KDA layer (state_rows, state_bytes_rw, tail_bytes_rw: what the
        rule needs, however the states moved); `state_update` says how
        they moved: `fused`, through their slots in one kernel a layer
        (`pallas_state.delta_decode_update`), or `gathered` by slot,
        advanced and scattered back (`delta_moe.fused_state`'s choice,
        asked again on the host). A latent layer attends its whole
        context (`LatentMoESet.note_decode`)."""
        from nnstreamer_tpu.llm.delta_moe import fused_state

        how = "fused" if fused_state(self.spec) else "gathered"
        self.counters[f"state_steps_{how}"] += 1
        return {**self._note_state(n), "state_update": how,
                **super().note_decode(pos_a, n)}

    def note_chunk(self, pos0: int, clen: int, bucket: int) -> dict:
        """One sequence's state and tails read and written once a KDA
        layer (counted so for a `fresh` chunk too, which starts from zero
        and reads neither), in `delta_runs` runs of the closed form a
        layer (the program's own count, from the run `chunk_kw` hands
        it); the latent layers' walk as `LatentMoESet.note_chunk`."""
        from nnstreamer_tpu.llm.delta_moe import RUN

        fresh = int(pos0) == 0
        runs = -(-bucket // min(RUN, bucket))
        self.counters["chunks_fresh"] += fresh
        self.counters["delta_runs"] += runs
        return {**self._note_state(1), "fresh": fresh, "delta_runs": runs,
                **super().note_chunk(pos0, clen, bucket)}


def _sparse_reads(spec, qpos: np.ndarray, slots: int) -> dict:
    """What the queries at positions `qpos` score, select and attend in
    one sparse layer of the hybrid family, a KV head; `slots` pool slots
    are gathered for them."""
    scored = int(np.maximum(
        (qpos + 1 - spec.ck_kernel) // spec.ck_stride + 1, 0).sum())
    blocks = np.minimum(spec.sel_topk, qpos // spec.sel_block + 1)
    # the query's own block holds qpos % sel_block + 1 tokens for it
    tokens = int(((blocks - 1) * spec.sel_block + qpos % spec.sel_block
                  + 1).sum())
    return {"ckeys_scored": scored, "blocks_selected": int(blocks.sum()),
            "kv_selected": tokens, "kv_slots": int(slots)}


@functools.lru_cache(maxsize=256)
def _chunk_reads(spec, pos0: int, clen: int, slots: int) -> dict:
    """A chunk's reads, which only its place, its length and the `slots`
    of its live context tiles decide, so each is reckoned once: those
    slots are what `hybrid_lm.sparse_attend_walk` gathers, a KV head,
    once for all of the chunk's queries."""
    return _sparse_reads(spec, pos0 + np.arange(clen, dtype=np.int64), slots)


#: `LMSpec.family` -> its program set
FAMILIES: Dict[str, type] = {DENSE: DenseSet, SPARSE_MOE: SparseMoESet,
                             HYBRID: HybridSet, WINDOW_MOE: WindowMoESet,
                             LATENT_MOE: LatentMoESet,
                             DELTA_MOE: DeltaMoESet}


def program_set(spec, *, name: str, **given):
    """The set serving a bundle's `spec` (None: a dense bundle that
    describes nothing), built from what the executor knows: `params`,
    `dtype`, `n_heads`, the pool's geometry, `kernel`, `shards`,
    `shard_fns`, and the engine's `rows` and prompt `chunk`."""
    family = DENSE if spec is None else spec.family
    cls = FAMILIES.get(family)
    if cls is None:
        raise BackendError(
            f"llm {name}: the bundle's spec names the family {family!r}; "
            f"this program serves {sorted(FAMILIES)}")
    return cls(spec, name=name, **given)
