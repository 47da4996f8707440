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
- `idx_dim`: the width a token needs of the pool's blocks beyond K and V;
- its accounting: `note_decode` and `note_beside` count what a call
  attended and read and return what its span says of it; `stats()` is
  the family's part of the executor's.

A new family is a subclass and an entry of `FAMILIES`; the executor, the
engine and the element are not edited (docs/llm_serving.md).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import numpy as np

from nnstreamer_tpu.core.errors import BackendError
from nnstreamer_tpu.llm.spec import DENSE, SPARSE_MOE


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
                 shard_fns: Callable[[], dict] = None):
        self.spec, self.name = spec, name
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
        self._walk_slots = None
        if not shards and kernel == "xla":
            from nnstreamer_tpu.llm.paged_model import walk_slots

            self._walk_slots = walk_slots

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
                   pools) -> tuple:
        return (params, ids, pos0, blk_idx, blk_off, tab, *pools, last)

    def decode_args(self, params, cur, tab, pos, n: int, pools) -> tuple:
        return (params, cur, tab, pos, *pools)

    def split(self, out: tuple) -> tuple:
        """A program's result as (logits, the device values it returns
        beside them, the pools)."""
        logits, *pools = out
        return logits, (), pools

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
        if self._walk_slots is not None:
            slots = self._walk_slots(pos_a, self.block_size, self.n_kv,
                                     self.head_dim, self.max_blocks)
        else:
            slots = len(pos_a) * self.max_blocks * self.block_size
        tokens = int(pos_a[:n].sum()) + n
        self.counters["kv_tokens_attended"] += tokens
        self.counters["kv_slots_read"] += slots
        return {"kv_tokens": tokens, "kv_slots": slots}

    def note_beside(self, kind: str, host: list) -> dict:
        """Account what a `chunk` or a `decode` returned beside its
        logits, now on the host, and return its span's part."""
        return {}

    def stats(self) -> dict:
        return dict(self.counters)


class SparseMoESet(DenseSet):
    """The sparse-expert decoder whose attention a learned indexer
    chooses (llm/sparse_moe.py): one prefill program, its chunk; a third
    pool of indexer keys; each call returns the tokens an expert got,
    (layers, experts), beside its logits."""

    family = SPARSE_MOE
    #: the longest prompt the one-chunk whole-prompt prefill takes: past
    #: it a chunk's (heads, C, tile) temporaries outgrow what the pool
    #: leaves free, and the engine has to chunk (prefill_chunk)
    WHOLE_PROMPT_MAX = 4096

    def __init__(self, spec, *, params: dict, **given):
        super().__init__(spec, params=params, **given)
        # what the family cannot yet be combined with (ROADMAP C2)
        why = None
        if self.shards > 0:
            why = (f"shards={self.shards}: its experts and indexer pool "
                   f"have no sharding rule yet (ROADMAP B2)")
        elif self.paged_kernel == "pallas":
            why = ("paged_kernel=pallas: it has no Pallas twin yet "
                   "(ROADMAP B2); set paged_kernel=xla")
        elif any(k.endswith("_scale") for k in params["blocks"][0]):
            why = ("a W8A8 store version: its grouped expert products "
                   "are float only")
        if why is not None:
            raise BackendError(
                f"llm {self.name}: the sparse_moe family cannot be served "
                f"with {why}")
        self.idx_dim = int(spec.idx_dim)
        self.kw = {"spec": spec, "dtype": self.kw["dtype"]}
        # kept tracer on or off. Decode steps: context slots the indexer
        # scored / slots selected and attended / indexer-pool slots a
        # layer read (kv_slots_read then counts the selected slots'
        # gathers); (layer, step) pairs and the distinct experts that
        # got a token in them. Every call: (token, expert) pairs routed.
        # Chunks: tokens at the busiest expert, summed over the chunks
        # whose counts have been read back (expert_load_chunks).
        self.counters.update(dict.fromkeys((
            "kv_tokens_scored", "kv_tokens_selected", "idx_slots_read",
            "expert_tokens", "expert_steps_layers", "experts_touched_sum",
            "expert_load_max_sum", "expert_load_chunks"), 0))

    def prefill_kind(self, params: dict) -> str:
        return "chunk"

    def program(self, kind: str) -> Program:
        from nnstreamer_tpu.llm import sparse_moe

        if kind == "chunk":
            return Program(sparse_moe.sparse_moe_prefill_chunk,
                           ("spec", "dtype", "by_block"), (6, 7, 8))
        return Program(sparse_moe.sparse_moe_decode_step,
                       ("spec", "dtype"), (5, 6, 7))

    def chunk_kw(self, pos0: int, bucket: int) -> dict:
        """Whole blocks are written at once where the chunk lies on
        them: every chunk of a prompt does when block_size divides
        prefill_chunk, so the bucket stays one program."""
        bs = self.block_size
        return dict(self.kw,
                    by_block=int(pos0) % bs == 0 and bucket % bs == 0)

    def decode_args(self, params, cur, tab, pos, n: int, pools) -> tuple:
        # n live rows: a step's padding rows reach no expert
        return (params, cur, tab, pos, np.int32(n), *pools)

    def split(self, out: tuple) -> tuple:
        logits, counts, *pools = out
        return logits, (counts,), pools

    def check_prompt(self, plen: int, prefill_chunk: int) -> None:
        """The family prefills through its chunk program only, and one
        chunk holds at most WHOLE_PROMPT_MAX."""
        if plen > self.WHOLE_PROMPT_MAX and not 0 < prefill_chunk < plen:
            raise BackendError(
                f"llm {self.name}: a prompt of {plen} tokens needs "
                f"chunked prefill in the sparse_moe family (one chunk "
                f"holds at most {self.WHOLE_PROMPT_MAX}); set "
                f"prefill_chunk (it is {prefill_chunk})")

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

    def note_beside(self, kind: str, host: list) -> dict:
        """One call's (layers, experts) token counts: distinct experts
        with a token, summed over layers, and for a chunk the tokens at
        the busiest expert, largest over layers."""
        counts, = host
        touched = int((counts > 0).sum())
        c = self.counters
        c["expert_tokens"] += int(counts.sum())
        if kind == "decode":
            c["expert_steps_layers"] += counts.shape[0]
            c["experts_touched_sum"] += touched
            return {"experts_touched": touched}
        load_max = int(counts.max())
        c["expert_load_max_sum"] += load_max
        c["expert_load_chunks"] += 1
        return {"experts_touched": touched, "expert_load_max": load_max}

    def stats(self) -> dict:
        return dict(self.counters, family=self.family)


#: `LMSpec.family` -> its program set
FAMILIES: Dict[str, type] = {DENSE: DenseSet, SPARSE_MOE: SparseMoESet}


def program_set(spec, *, name: str, **given):
    """The set serving a bundle's `spec` (None: a dense bundle that
    describes nothing), built from what the executor knows: `params`,
    `dtype`, `n_heads`, the pool's geometry, `kernel`, `shards` and
    `shard_fns`."""
    family = DENSE if spec is None else spec.family
    cls = FAMILIES.get(family)
    if cls is None:
        raise BackendError(
            f"llm {name}: the bundle's spec names the family {family!r}; "
            f"this program serves {sorted(FAMILIES)}")
    return cls(spec, name=name, **given)
