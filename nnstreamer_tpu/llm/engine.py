"""Continuous-batching generation engine (host half).

One `step()` is the serving quantum: admit queued requests while blocks
and batch slots allow (each admission runs a bucketed prefill and
yields its first token), then run ONE decode step for every in-flight
sequence — freshly admitted requests merge into the same decode batch
that step, and finished sequences retire immediately, returning their
blocks to the pool.

A sequence holds the blocks of what it has written, not of its whole
life: those of its prompt and first decode write at admission, one more
before the launch whose write position reaches the end of its table
(`_grow`). Admission looks ahead instead of at the free list: every row
of a launch advances one position, and a row's remaining launches are
known (its budget less its tokens out and in flight; an `eos_id` only
shortens them), so the blocks the admitted rows will hold at each later
launch are known too, a prompt still chunk-prefilling counted at its
whole life. The head of the queue is admitted iff the peak of that
demand, with it included, fits the pool (`paged_cache.peak_demand`). A
growth grant therefore cannot fail (the cache raises if one does), no
row is ever preempted, and since no term exceeds the row's whole life
no request waits longer than under whole-life reservation. FIFO and
head-of-line order are unchanged.

A model whose window layers keep pools of their own (llm/window_moe.py)
gives a request a second table, of those pools, and everything above
holds in each pool apart: admission is by the admitted rows' peak demand
in both (a window table's demand capped: `PagedKVCache.window_cap`), a
table grows before the launch that writes past its end, and the window
table's blocks wholly behind the window are given back (`_trim`) right
after the launch that last needed them is dispatched: launches run in
order on the device, so whoever is granted such a block next writes it
after that launch has read it. A family with one table takes none of
these branches.

Contrast `static_batching=True`, the A/B baseline:
a batch admits only while the engine is empty and runs to full
completion, so one long request holds the whole batch hostage (exactly
the head-of-line blocking continuous batching removes — bench family
`llm_serve` measures the gap).

Long prompts can optionally *chunk-prefill* (``prefill_chunk=N``): a
prompt longer than N tokens admits into a ``prefilling`` state and
advances one N-token chunk per step — through the executor's one
``llmp_chunk`` bucket — while the decode batch keeps stepping, so an
s8192 prompt stops being head-of-line for every live sequence's
inter-token latency. The final chunk's logits yield the first token.
``chunk_every=K`` spaces the chunks: while rows decode, a chunk rides
every K-th step and the steps between are the decode batch alone, so a
row's token rate is K tokens a (chunk + K decode steps) and not one a
chunk; with no row decoding a chunk rides every step. Where a chunk
costs many decode steps (a 2048-token chunk of a large model beside a
decode step bound by the weights' read) K = 1 holds every live row to
the chunk's pace for as long as any prompt prefills.

One step runs one launch ahead of its read-back. While every live row is
greedy (temperature 0) on a single chip, a step admits, launches this
step's decode, and only then reads the *previous* launch: each row's
token is the `argmax` of its logits taken on the device
(llm/next_ids.py: the lowest index among equal maxima, as `np.argmax`
and the `jnp.argmax` inside `transformer.generate`'s fused decode — a
parity requirement, not a convenience), it stays there to feed the next
launch, and the host reads `rows x 4` bytes of ids while the device is
already in the next step. The host knows every row's token count without
the values, so a row whose budget ends with the token in flight is not
launched again; a row that stops on `eos_id` was, and that one token is
discarded (`lookahead_discarded`). Tokens reach `step()`'s caller one
step after their launch.

A step that holds a row with temperature > 0 resolves first and samples
on the host from that step's (vocab,) f32 logits, as does every step
under `shards`: the sampled token comes from a
per-request seeded Generator (so a request's tokens don't depend on its
batchmates) and must be known before the next launch. Which of the two
orders a step takes is read from its rows, never set.

Host syncs are batched: every prefill/chunk launched in a step returns
*device* logits, and one `runtime.sync.device_sync` resolves a launch's
ids together with the first ids of the prefills launched beside it (or,
in a sampled step, one over the prefills' logits and one over the
decode batch's).

Every decode launch is accounted for row by row (`stats()["rows"]`,
always on, integers only): of the `max_batch` rows a launch could have
carried, each stood under exactly one state: `decode` (in the launch),
`prefilling` (held by an admitted request whose prompt is not through),
`retiring` (held at this step's admission, not carried: a last token in
flight, or released since), or free under the one cause this step's
admission found: `blocked` (the queue's head is short of KV blocks),
`blocked_state` (short of a state slot), `blocked_window` (short of
window blocks), `unfed` (the engine's queue is empty) or `other` (a static batch still running). The states sum to
`total`, and `total` is `max_batch` x the executor's `decode_steps`.
A request's stages (`queued_ms`, `prefill_wait_ms`, `prefill_ms`, their
sum `first_token_ms`, and `inter_token_ms`) are kept for the last
`STAGE_WINDOW` samples each. Under an active tracer the same account is
one `llm:<name>:rows` counter event a launch and one `prefill_wait` and
one `prefill` span a request (docs/observability.md).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, List, Optional

import numpy as np

from nnstreamer_tpu.core.errors import BackendError
from nnstreamer_tpu.core.log import get_logger
from nnstreamer_tpu.llm.paged_cache import peak_demand
from nnstreamer_tpu.runtime.sync import device_sync
from nnstreamer_tpu.runtime.tracing import NULL_TRACER, percentile

log = get_logger("llm.engine")

#: the states of a decode launch's rows (module docstring); the last
#: five are the causes a free row stands under
ROW_STATES = ("decode", "prefilling", "retiring",
              "blocked", "blocked_state", "blocked_window", "unfed", "other")

#: a request's stages, as `stats()` names their percentiles (ms)
STAGES = ("queued_ms", "prefill_wait_ms", "prefill_ms", "first_token_ms",
          "inter_token_ms")

#: samples a stage keeps: the latest, as the tracer's interlatency
#: reservoirs. Not the tracer's `_Hist`: its bounds end at 10 s and lie
#: a factor of 2.15 apart, where a long prompt waits tens of seconds for
#: its chunk turn and a step's 10 % is what is looked for.
STAGE_WINDOW = 8192


@dataclass
class LLMRequest:
    """One generation request plus its runtime serving state."""

    req_id: str
    prompt: np.ndarray                  # (plen,) int32, plen >= 1
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    eos_id: Optional[int] = None
    pts: Optional[int] = None           # carried through to emissions
    # -- runtime state (engine-owned) --
    tokens: List[int] = field(default_factory=list)
    state: str = "queued"          # queued | prefilling | active | done
    finish_reason: Optional[str] = None  # eos | length
    block_table: List[int] = field(default_factory=list)
    state_slot: Optional[int] = None    # where the model keeps a state
    # the table of the window layers' pools, where the model keeps those,
    # by a position's block as `block_table`; the entries before
    # `window_first` are given back and read the scratch block
    window_table: List[int] = field(default_factory=list)
    window_first: int = 0
    pos: int = 0                        # next cache write position
    t_submit: float = 0.0
    t_admit: float = 0.0                # rows, blocks (and slot) granted
    t_prefill0: float = 0.0             # launch of its first chunk / prefill
    t_first: Optional[float] = None
    t_last: float = 0.0
    itl_ms: List[float] = field(default_factory=list)
    ahead: int = 0                      # tokens launched, not yet read back
    # (engine steps, executor chunk launches) at admission, then at the
    # first launch: what the stage spans say of `steps` and `chunks`
    mark: tuple = (0, 0)
    _rng: Any = None

    @property
    def first_token_ms(self) -> Optional[float]:
        if self.t_first is None:
            return None
        return (self.t_first - self.t_submit) * 1e3

    def summary(self) -> dict:
        return {
            "req_id": self.req_id,
            "state": self.state,
            "finish_reason": self.finish_reason,
            "prompt_len": int(self.prompt.shape[0]),
            "n_tokens": len(self.tokens),
            "first_token_ms": self.first_token_ms,
            "itl_p50_ms": percentile(sorted(self.itl_ms), 50)
            if self.itl_ms else None,
        }


@dataclass
class TokenEvent:
    """`step()` output: new tokens for one request (done ⇒ final)."""

    request: LLMRequest
    tokens: List[int]
    done: bool


class _Picked:
    """Stands where a row's logits stood when their argmax was taken on
    the device: the `token`, and the logits' `shape`. `_sample` stays
    the one place every token of every row passes."""

    __slots__ = ("token", "shape")

    def __init__(self, token: int, vocab: int):
        self.token, self.shape = token, (vocab,)


@dataclass
class _Ahead:
    """What a step launched and left for the next step to read: the
    first ids of its prefills, its decode launch (None: no row had a
    token left to decode), and that launch's rows in order."""

    firsts: List[tuple]                 # (request, device id)
    launch: Any
    rows: List[LLMRequest]


class LLMEngine:
    """Admission + continuous-batching loop over a PagedLLMExecutor."""

    def __init__(self, model="store://transformer", *, n_heads: int = 4,
                 dtype=None, block_size: int = 16, num_blocks: int = 64,
                 max_batch: int = 8, max_len: int = 128,
                 static_batching: bool = False, prefill_chunk: int = 0,
                 chunk_every: int = 1,
                 paged_kernel: Optional[str] = None, shards: int = 0,
                 shard_chips=None, ring_prefill_min: int = 0,
                 tracer=NULL_TRACER, name: str = "llm"):
        from nnstreamer_tpu.backends.llm_exec import PagedLLMExecutor

        self.name = name
        self.tracer = tracer
        self.max_batch = int(max_batch)
        self.static = bool(static_batching)
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 0:
            raise BackendError(
                f"prefill_chunk must be >= 0, got {self.prefill_chunk}")
        self.chunk_every = int(chunk_every)
        if self.chunk_every < 1:
            raise BackendError(
                f"chunk_every must be >= 1, got {self.chunk_every}")
        #: steps since a chunk last rode one
        self._since_chunk = 0
        if int(shards) > 0 and self.prefill_chunk > 0:
            raise BackendError(
                f"llm {name}: prefill_chunk and shards are exclusive — "
                f"sharded long prompts go through the sequence-parallel "
                f"ring prefill (ring_prefill_min), not chunking")
        self.executor = PagedLLMExecutor(
            model, n_heads=n_heads, dtype=dtype, block_size=block_size,
            num_blocks=num_blocks, max_len=max_len,
            paged_kernel=paged_kernel, shards=shards,
            shard_chips=shard_chips, ring_prefill_min=ring_prefill_min,
            # a model that keeps a state a sequence: a slot for each row;
            # one with window pools sizes them by the rows and the chunk
            state_slots=self.max_batch, prefill_chunk=self.prefill_chunk,
            tracer=tracer, name=name)
        self.cache = self.executor.cache
        self.queue: deque = deque()
        self.active: List[LLMRequest] = []
        self.prefilling: List[LLMRequest] = []
        self._seq = 0
        self.submitted = 0
        self.finished = 0
        self.tokens_out = 0
        self.steps = 0
        # admissions that waited: short of KV blocks, short of a state
        # slot, short of window blocks
        self.admission_blocked = 0
        self.admission_blocked_state = 0
        self.admission_blocked_window = 0
        #: the launch the next step reads (module docstring)
        self._ahead: Optional[_Ahead] = None
        # decode launches made while the one before was still unread,
        # and tokens computed for a row that had stopped on its eos_id
        self.lookahead_steps = 0
        self.lookahead_discarded = 0
        #: row-steps by state, cumulative (module docstring)
        self.rows = dict.fromkeys(ROW_STATES + ("total",), 0)
        # what this step's admission left free, and why
        self._free_rows = 0
        self._free_cause = "unfed"
        # steps in which a prompt waited and `chunk_every` held its chunk
        self.chunk_deferred_steps = 0
        self._stage = {k: deque(maxlen=STAGE_WINDOW) for k in STAGES}

    # -- submission --------------------------------------------------------
    def submit(self, prompt, *, req_id: Optional[str] = None,
               max_new_tokens: int = 32, temperature: float = 0.0,
               top_k: int = 0, seed: int = 0,
               eos_id: Optional[int] = None,
               pts: Optional[int] = None) -> LLMRequest:
        """Queue a request. Rejects (raises) only what can NEVER be
        served — a prompt+budget exceeding per-sequence table capacity,
        or the pool alone; a pool whose admitted rows' peak demand
        leaves no room yet queues instead."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.shape[0] == 0:
            raise BackendError("llm request needs a non-empty prompt")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise BackendError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        ex = self.executor
        ex.programs.check_prompt(int(prompt.shape[0]), self.prefill_chunk)
        total = int(prompt.shape[0]) + max_new_tokens
        seq_cap = ex.max_blocks * self.cache.block_size
        if total > seq_cap:
            raise BackendError(
                f"request needs {total} token slots but max_len={ex.max_len} "
                f"caps a sequence at {seq_cap}; raise max_len/num_blocks "
                f"or shorten the request")
        if self.cache.blocks_for(total) > self.cache.allocator.total:
            raise BackendError(
                f"request needs {self.cache.blocks_for(total)} blocks but "
                f"the pool only has {self.cache.allocator.total}")
        if self._windowed and not self._chunked(int(prompt.shape[0])) \
                and self.cache.blocks_for(int(prompt.shape[0]) + 1) \
                > self.cache.window_alloc.total:
            raise BackendError(
                f"a whole prompt of {prompt.shape[0]} tokens needs more "
                f"window blocks than the {self.cache.window_alloc.total} "
                f"there are; set prefill_chunk")
        if req_id is None:
            self._seq += 1
            req_id = f"{self.name}-{self._seq}"
        req = LLMRequest(
            req_id=req_id, prompt=prompt, max_new_tokens=max_new_tokens,
            temperature=float(temperature), top_k=int(top_k),
            seed=int(seed), eos_id=None if eos_id is None else int(eos_id),
            pts=pts)
        req.t_submit = time.perf_counter()
        self.queue.append(req)
        self.submitted += 1
        return req

    def prewarm(self, max_prompt: Optional[int] = None) -> int:
        """Compile all decode buckets (up to max_batch) and prefill
        buckets (up to `max_prompt`, default max_len) ahead of traffic."""
        return self.executor.prewarm_buckets(
            max_batch=self.max_batch,
            max_prompt=max_prompt or self.executor.max_len,
            chunk=self.prefill_chunk)

    # -- the serving quantum ----------------------------------------------
    @property
    def has_work(self) -> bool:
        return bool(self.queue or self.active or self.prefilling
                    or self._ahead)

    def step(self) -> List[TokenEvent]:
        """Admit, prefill (whole or one chunk of a long prompt), launch
        one decode step, read the launch before it, retire. Returns the
        token events of what was read: a request's prefill token and
        its first decode token come together, a step after its
        admission (in a sampled step: at once, as every other token);
        chunk-prefilling requests emit nothing until their final chunk
        lands."""
        events: List[TokenEvent] = []
        if self._ahead is not None and self.executor.swap_due():
            # a swap lands between two whole steps of one version
            self._resolve_ahead(events)
        self.executor.maybe_adopt()
        #: (req, device logits) for every prefill completed this step
        pending: List[tuple] = []
        self._admit(pending)
        self._free_rows = (self.max_batch - len(self.active)
                           - len(self.prefilling) - len(pending))
        if self.prefilling:
            # rows that hold a place and join chunks from now: the decode
            # bucket of all the rows admitted is built here, where fewer
            # rows wait on it than at the step the batch is largest
            self.executor.warm_decode(len(self.active) + len(pending)
                                      + len(self.prefilling))
        self._prefill_chunks(pending)
        if self._runs_ahead(pending):
            ahead = self._launch_ahead(pending)
            self._resolve_ahead(events)
            self._ahead = ahead
        else:
            self._resolve_ahead(events)
            self._finish_pending(pending, events)
            self._decode(events)
        self.steps += 1
        return events

    def drain(self, max_steps: int = 100000) -> List[TokenEvent]:
        """Run steps until idle (EOS / element flush path)."""
        events: List[TokenEvent] = []
        steps = 0
        while self.has_work:
            events.extend(self.step())
            steps += 1
            if steps >= max_steps:
                raise BackendError(
                    f"llm drain did not converge in {max_steps} steps "
                    f"({len(self.active)} active, {len(self.queue)} queued)")
        return events

    def _admit(self, pending: List[tuple]) -> None:
        """Admission, and under an active tracer one span over the whole
        call (prefill launches included) whose label is the outcome:
        `admit` (at least one request admitted), `admit_blocked` (the
        head of the queue is short of KV blocks), `admit_blocked_state`
        (it is short of a state slot), `admit_blocked_window` (of window
        blocks), `admit_none_queued`
        (a row is free and nothing is queued here: whatever waits is
        still upstream of the engine) or `admit_full` (no row to give:
        all live, or a static batch still running)."""
        tr = self.tracer
        if not tr.active:
            return self._admit_queue(pending)
        t0 = time.perf_counter()
        prefilling = len(self.prefilling)
        rows = len(self.active) + prefilling + len(pending)
        queued, blocked = len(self.queue), self.admission_blocked
        blocked_state = self.admission_blocked_state
        blocked_window = self.admission_blocked_window
        self._admit_queue(pending)
        admitted = queued - len(self.queue)
        if admitted:
            label = "admit"
        elif self.admission_blocked > blocked:
            label = "admit_blocked"
        elif self.admission_blocked_state > blocked_state:
            label = "admit_blocked_state"
        elif self.admission_blocked_window > blocked_window:
            label = "admit_blocked_window"
        elif not queued and rows < self.max_batch:
            label = "admit_none_queued"
        else:
            label = "admit_full"
        args = {}
        if self.cache.state_alloc is not None:
            args["state_free"] = self.cache.state_alloc.free
        if self._windowed:
            args["window_free"] = self.cache.window_alloc.free
        tr.span("llm", self.name, label, t0, time.perf_counter(),
                step=self.steps, rows=rows, prefilling=prefilling,
                queued=queued, admitted=admitted,
                blocks_free=self.cache.allocator.free, **args)

    def _admit_queue(self, pending: List[tuple]) -> None:
        """Admit from the head of the queue while rows, blocks and
        state slots allow, and leave in `_free_cause` why a row it
        left free stands empty."""
        # static A/B mode: the batch forms only from empty, no top-up
        if self.static and (self.active or self.prefilling):
            self._free_cause = "other"
            return
        # the loop ends with the queue empty, or with no row left free
        self._free_cause = "unfed"
        # pending holds this step's already-admitted prefills (they only
        # join active in _finish_pending) — count them against the cap
        while self.queue and (len(self.active) + len(self.prefilling)
                              + len(pending) < self.max_batch):
            req = self.queue[0]
            plen = int(req.prompt.shape[0])
            # the prompt's blocks and the first decode write's; the rest
            # as it grows, which the peak says the pool can give
            joined = [r for r, _ in pending]
            window = self._window_ask(req, joined) if self._windowed else {}
            got = self.cache.reserve(
                self.cache.blocks_for(plen + 1), owner=req.req_id,
                peak=self._peak_with(req, joined), **window)
            if isinstance(got, str):
                # head-of-line waits for retirements; admitting a
                # smaller later request instead would starve it
                if got == "state":
                    self.admission_blocked_state += 1
                    self._free_cause = "blocked_state"
                elif got == "window":
                    self.admission_blocked_window += 1
                    self._free_cause = "blocked_window"
                else:
                    self.admission_blocked += 1
                    self._free_cause = "blocked"
                return
            blocks, req.state_slot, *rest = got
            if rest:
                req.window_table, req.window_first = rest[0], 0
            self.queue.popleft()
            req.t_admit = time.perf_counter()
            req.mark = (self.steps, self.executor.chunk_prefills)
            if self.tracer.active:
                self.tracer.span("llm", self.name, "queued", req.t_submit,
                                 req.t_admit, req=req.req_id)
            req.block_table = blocks
            if self._chunked(plen):
                # long prompt: prefill one chunk per step alongside the
                # decode batch instead of head-of-line blocking it
                req.state = "prefilling"
                req.pos = 0
                self.prefilling.append(req)
                continue
            req.state = "active"
            # a whole prefill is launched where it is admitted
            self._first_launch(req, req.t_admit)
            logits = self.executor.prefill(
                req.prompt, blocks, sync=False, req=req.req_id,
                state_slot=req.state_slot, **self._window_of(req))
            req.pos = plen
            self._trim(req)
            pending.append((req, logits))

    def _peak_with(self, req: LLMRequest, pending: List[LLMRequest]) -> int:
        """The most blocks the admitted rows and `req` will hold
        together (module docstring). A row's launches left count its
        tokens in flight; a row this step admitted, like `req`, has its
        first token still to come from its prefill and is counted one
        launch long. A prompt that is not through, `req`'s too if it
        will chunk, holds its whole life throughout."""
        rows = [(r.pos, r.max_new_tokens - len(r.tokens) - r.ahead)
                for r in self.active + pending]
        whole = list(self.prefilling)
        plen = int(req.prompt.shape[0])
        if self._chunked(plen):
            whole.append(req)
        else:
            rows.append((plen, req.max_new_tokens))
        return peak_demand(rows, self.cache.block_size, held=sum(
            self.cache.blocks_for(int(r.prompt.shape[0]) + r.max_new_tokens)
            for r in whole))

    @property
    def _windowed(self) -> bool:
        """Whether the model keeps window pools: a second table a row."""
        return self.cache.window_alloc is not None

    def _window_ask(self, req: LLMRequest, pending: List[LLMRequest]) -> dict:
        """What admitting `req` asks of the window pools, as `reserve`'s
        keywords: the blocks of a whole prompt and its first decode
        write now (a prompt that will chunk grows into its own before
        each chunk), and the most window blocks the admitted rows and
        `req` will hold together: `_peak_with`'s account with every
        table capped at a decoding row's cap, a prompt not yet through
        at the least of its whole life and that cap, and once, for the
        one prompt whose chunk is being computed, what a chunk's cap
        adds."""
        cache = self.cache
        cap = cache.window_cap(1)
        rows = [(r.pos, r.max_new_tokens - len(r.tokens) - r.ahead)
                for r in self.active + pending]
        whole = list(self.prefilling)
        plen = int(req.prompt.shape[0])
        ask = 0
        if self._chunked(plen):
            whole.append(req)
        else:
            rows.append((plen, req.max_new_tokens))
            ask = cache.blocks_for(plen + 1)
        held = sum(min(cap, cache.blocks_for(
            int(r.prompt.shape[0]) + r.max_new_tokens)) for r in whole)
        if whole:
            held += cache.window_cap(self.prefill_chunk) - cap
        return {"window": ask, "window_peak": peak_demand(
            rows, cache.block_size, held=held, cap=cap)}

    def _window_of(self, req: LLMRequest) -> dict:
        """A request's window table, as the executor's keyword."""
        return {"window_table": req.window_table} if self._windowed else {}

    def _trim(self, req: LLMRequest) -> None:
        """After the dispatch of the launch that wrote up to `req.pos`:
        the next launch queries from `req.pos` on, and the window blocks
        wholly behind its window go back."""
        if self._windowed:
            req.window_first = self.cache.trim(
                req.window_table, req.pos, req.window_first)

    def _chunked(self, plen: int) -> bool:
        """Whether a prompt of `plen` tokens prefills in chunks."""
        return 0 < self.prefill_chunk < plen

    def _grow(self, rows: List[LLMRequest]) -> None:
        """Before a decode launch: a block more for every row of it
        whose write position has reached the end of its table."""
        bs = self.cache.block_size
        windowed = self._windowed
        for r in rows:
            if r.pos // bs == len(r.block_table):
                self.cache.grow(r.block_table, owner=r.req_id)
            if windowed and r.pos // bs == len(r.window_table):
                self.cache.grow(r.window_table, owner=r.req_id, window=True)

    def _prefill_chunks(self, pending: List[tuple]) -> None:
        """Advance the oldest chunk-prefilling prompt by ONE chunk (the
        per-step prefill compute budget that keeps decode stepping);
        when its final chunk lands, its logits join this step's pending
        batch and the request enters the decode batch. While rows
        decode, only every `chunk_every`-th step carries one."""
        if not self.prefilling:
            return
        self._since_chunk += 1
        if self.active and self._since_chunk < self.chunk_every:
            self.chunk_deferred_steps += 1
            return
        self._since_chunk = 0
        req = self.prefilling[0]
        plen = int(req.prompt.shape[0])
        if req.pos == 0:
            self._first_launch(req, time.perf_counter())
        chunk = req.prompt[req.pos:req.pos + self.prefill_chunk]
        from nnstreamer_tpu.backends.xla import next_pow2

        if self._windowed:
            # the window table grows into the chunk (`_grow` sees to the
            # first decode write's block)
            upto = req.pos + int(chunk.shape[0])
            while len(req.window_table) < self.cache.blocks_for(upto):
                self.cache.grow(req.window_table, owner=req.req_id,
                                window=True)
        logits = self.executor.prefill_chunk(
            chunk, req.pos, req.block_table,
            bucket=next_pow2(self.prefill_chunk, 8), sync=False,
            req=req.req_id, state_slot=req.state_slot,
            **self._window_of(req))
        req.pos += int(chunk.shape[0])
        self._trim(req)
        if req.pos >= plen:
            self.prefilling.pop(0)
            req.state = "active"
            pending.append((req, logits))

    def _finish_pending(self, pending: List[tuple],
                        events: List[TokenEvent]) -> None:
        """ONE whole-batch device sync over every prefill the step
        launched, then sample first tokens host-side. Freshly finished
        requests join `active` here and merge into the same step's
        decode batch."""
        if not pending:
            return
        tr = self.tracer
        t0 = time.perf_counter() if tr.active else 0.0
        arrays = device_sync(
            [lg for _, lg in pending], tracer=tr,
            name=f"{self.name}:prefill_batch")
        t1 = time.perf_counter() if tr.active else 0.0
        for (req, _), lg in zip(pending, arrays):
            tok = self._sample(req, np.asarray(lg))
            self._record_token(req, tok)
            self.active.append(req)
            done = self._maybe_finish(req, tok)
            events.append(TokenEvent(req, [tok], done))
        if tr.active:
            tr.span("backend", self.name, "wait", t0, t1,
                    what="llm_prefill_batch")
            self._sample_span(t1, len(pending))

    def _runs_ahead(self, pending: List[tuple]) -> bool:
        """Whether this step may launch before it reads: every row it
        holds is greedy, on one chip."""
        if self.executor.shards:
            return False
        rows = self.active + [r for r, _ in pending]
        return not any(r.temperature > 0.0 for r in rows)

    def _launch_ahead(self, pending: List[tuple]) -> Optional[_Ahead]:
        """Take each finished prefill's first token on the device, then
        launch one decode step for every row that has a token left once
        those in flight are counted. Nothing is waited for."""
        ex = self.executor
        firsts = []
        for req, logits in pending:
            firsts.append((req, ex.pick_first(logits, req.block_table)))
            req.ahead += 1
            self.active.append(req)
        rows = [r for r in self.active
                if len(r.tokens) + r.ahead < r.max_new_tokens]
        launch = None
        if rows:
            self._account_rows(len(rows))
            self._grow(rows)
            launch = ex.decode(
                [None if r.ahead else r.tokens[-1] for r in rows],
                [r.block_table for r in rows], [r.pos for r in rows],
                sync=False, state_slots=self._state_slots(rows),
                **self._window_tables(rows))
            if self._ahead is not None and self._ahead.launch is not None:
                self.lookahead_steps += 1
            for r in rows:
                r.pos += 1
                r.ahead += 1
                self._trim(r)
        # a row whose budget ends with a token in flight is read by no
        # later launch: its row and blocks go back now, for the next
        # step's admission as in the synchronous order (whatever is
        # prefilled into them runs after the launches already made)
        for r in [r for r in self.active
                  if len(r.tokens) + r.ahead >= r.max_new_tokens]:
            self._release(r)
        if launch is None and not firsts:
            return None
        return _Ahead(firsts, launch, rows)

    def _resolve_ahead(self, events: List[TokenEvent]) -> None:
        """Read the outstanding launch, if there is one: its prefills'
        first tokens, then its rows' tokens. A row that stopped on its
        eos_id after the launch was made has its token discarded; its
        write went to a block it still owned, and the device runs a
        later prefill into that block after it."""
        ahead, self._ahead = self._ahead, None
        if ahead is None:
            return
        ids, first_ids = self.executor.resolve(
            ahead.launch, [dev for _, dev in ahead.firsts])
        t0 = time.perf_counter() if self.tracer.active else 0.0
        taken = [(req, tok) for (req, _), tok
                 in zip(ahead.firsts, first_ids)]
        if ids is not None:
            taken.extend(zip(ahead.rows, ids))
        vocab = self.executor.vocab
        for req, tok in taken:
            req.ahead -= 1
            if req.state != "active":
                self.lookahead_discarded += 1
                continue
            tok = self._sample(req, _Picked(int(tok), vocab))
            self._record_token(req, tok)
            done = self._maybe_finish(req, tok)
            events.append(TokenEvent(req, [tok], done))
        if self.tracer.active:
            self._sample_span(t0, len(taken))

    def _decode(self, events: List[TokenEvent]) -> None:
        live = [r for r in self.active if r.state == "active"]
        if not live:
            return
        self._account_rows(len(live))
        self._grow(live)
        logits = self.executor.decode(
            [r.tokens[-1] for r in live],
            [r.block_table for r in live],
            [r.pos for r in live], state_slots=self._state_slots(live),
            **self._window_tables(live))
        t0 = time.perf_counter() if self.tracer.active else 0.0
        for i, req in enumerate(live):
            req.pos += 1
            self._trim(req)
            tok = self._sample(req, logits[i])
            self._record_token(req, tok)
            done = self._maybe_finish(req, tok)
            events.append(TokenEvent(req, [tok], done))
        if self.tracer.active:
            self._sample_span(t0, len(live))

    # -- helpers -----------------------------------------------------------
    def _account_rows(self, decode: int) -> None:
        """One decode launch of `decode` rows: `max_batch` row-steps,
        each under one state (module docstring). What admission found
        free stands under its cause; what it found held and the launch
        does not carry is prefilling or retiring."""
        acc = self.rows
        prefilling = len(self.prefilling)
        free = self._free_rows
        retiring = self.max_batch - decode - prefilling - free
        acc["decode"] += decode
        acc["prefilling"] += prefilling
        acc["retiring"] += retiring
        acc[self._free_cause] += free
        acc["total"] += self.max_batch
        if self.tracer.active:
            self.tracer.counter(
                "llm", self.name, "rows", time.perf_counter(),
                dict(decode=decode, prefilling=prefilling,
                     retiring=retiring, free=free),
                cause=self._free_cause if free else None,
                queued=len(self.queue), step=self.steps)

    def _first_launch(self, req: LLMRequest, t: float) -> None:
        """The launch of a request's whole prefill or of its prompt's
        first chunk, at `t`: the wait for a chunk turn ends here."""
        req.t_prefill0 = t
        steps, chunks = req.mark
        req.mark = (self.steps, self.executor.chunk_prefills)
        if self.tracer.active:
            self.tracer.span(
                "llm", self.name, "prefill_wait", req.t_admit, t,
                req=req.req_id, steps=self.steps - steps,
                chunks=self.executor.chunk_prefills - chunks)

    def _sample_span(self, t0: float, rows: int) -> None:
        """The host's loop over a step's rows (sampling, token
        bookkeeping, retirement with its block frees), as one span."""
        self.tracer.span("llm", self.name, "sample", t0,
                         time.perf_counter(), step=self.steps, rows=rows)

    def _sample(self, req: LLMRequest, logits) -> int:
        if isinstance(logits, _Picked):
            return logits.token
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        lg = logits.astype(np.float64) / req.temperature
        if req.top_k > 0 and req.top_k < lg.shape[0]:
            kth = np.partition(lg, -req.top_k)[-req.top_k]
            lg = np.where(lg < kth, -np.inf, lg)
        lg -= lg.max()
        p = np.exp(lg)
        p /= p.sum()
        if req._rng is None:
            req._rng = np.random.default_rng(req.seed)
        return int(req._rng.choice(lg.shape[0], p=p))

    def _record_token(self, req: LLMRequest, tok: int) -> None:
        now = time.perf_counter()
        if req.t_first is None:
            req.t_first = now
            stage = self._stage
            stage["queued_ms"].append((req.t_admit - req.t_submit) * 1e3)
            stage["prefill_wait_ms"].append(
                (req.t_prefill0 - req.t_admit) * 1e3)
            stage["prefill_ms"].append((now - req.t_prefill0) * 1e3)
            stage["first_token_ms"].append(req.first_token_ms)
            if self.tracer.active:
                plen, chunk = int(req.prompt.shape[0]), self.prefill_chunk
                self.tracer.span(
                    "llm", self.name, "prefill", req.t_prefill0, now,
                    req=req.req_id, steps=self.steps - req.mark[0],
                    chunks=-(-plen // chunk) if 0 < chunk < plen else 1)
                self.tracer.instant(
                    self.name, "first_token", t=now, req=req.req_id,
                    ms=round(req.first_token_ms, 3))
        else:
            itl = (now - req.t_last) * 1e3
            req.itl_ms.append(itl)
            self._stage["inter_token_ms"].append(itl)
        req.t_last = now
        req.tokens.append(tok)
        self.tokens_out += 1

    def _maybe_finish(self, req: LLMRequest, tok: int) -> bool:
        if req.eos_id is not None and tok == req.eos_id:
            req.finish_reason = "eos"
        elif len(req.tokens) >= req.max_new_tokens:
            req.finish_reason = "length"
        else:
            return False
        req.state = "done"
        if req.block_table:
            self._release(req)
        self.finished += 1
        if self.tracer.active:
            self.tracer.record_llm_request(
                self.name, req.req_id, time.perf_counter(),
                **{k: v for k, v in req.summary().items()
                   if k != "req_id"})
        return True

    def _state_slots(self, rows: List[LLMRequest]) -> Optional[List[int]]:
        """The rows' state slots, where the model keeps a state a
        sequence."""
        if self.cache.state_alloc is None:
            return None
        return [r.state_slot for r in rows]

    def _window_tables(self, rows: List[LLMRequest]) -> dict:
        """The rows' window tables, as the executor's keyword, where the
        model keeps window pools."""
        if not self._windowed:
            return {}
        return {"window_tables": [r.window_table for r in rows]}

    def _release(self, req: LLMRequest) -> None:
        """Give a request's row, blocks and state slot back: when it
        finishes, or ahead of that once its last token is in flight
        (what is prefilled into them runs after the launches already
        made, the state's first chunk starting from zero)."""
        if self._windowed:
            self.cache.release(req.block_table, req.state_slot,
                               req.window_table[req.window_first:])
            req.window_table, req.window_first = [], 0
        else:
            self.cache.release(req.block_table, req.state_slot)
        req.block_table, req.state_slot = [], None
        self.active.remove(req)

    def stats(self) -> dict:
        out = {
            "submitted": self.submitted,
            "finished": self.finished,
            "queued": len(self.queue),
            "active": len(self.active),
            "prefilling": len(self.prefilling),
            "tokens_out": self.tokens_out,
            "steps": self.steps,
            "admission_blocked": self.admission_blocked,
            "admission_blocked_state": self.admission_blocked_state,
            "admission_blocked_window": self.admission_blocked_window,
            "rows": dict(self.rows),
            "chunk_deferred_steps": self.chunk_deferred_steps,
            "scheduling": "static" if self.static else "continuous",
            "prefill_chunk": self.prefill_chunk,
            "chunk_every": self.chunk_every,
            "lookahead_steps": self.lookahead_steps,
            "lookahead_discarded": self.lookahead_discarded,
            "cache": self.cache.stats(),
            "executor": self.executor.stats(),
        }
        for key, window in self._stage.items():
            if window:
                vals = sorted(window)
                out[key] = {f"p{p}": round(percentile(vals, p), 3)
                            for p in (50, 95, 99)}
        return out
