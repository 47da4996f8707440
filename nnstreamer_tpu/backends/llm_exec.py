"""Paged-LLM executor: bucketed, version-namespaced prefill/decode jits.

The LLM engine's device half. Owns the paged KV pools and a jit cache
keyed ``(namespace, kind, bucket)`` where namespace is ``("v", version)``
for ``store://`` models and ``("g", 0)`` otherwise — the same
namespacing discipline as the XLA filter backend (backends/xla.py), so
model-store hot swap composes: the store's swap controller calls
``prewarm_version`` on this handle before the epoch flips, and the
engine adopts at a step boundary (one scheduler thread ⇒ a step sees
exactly one version snapshot).

``shards=N`` opens the executor tensor-parallel over N chips
(serving/sharding.py): projections are served canonically blocked and
head-sharded, the KV pools are sharded along the kv-head axis next to
them, and the jit namespace becomes ``("tp", N, version)`` — same
per-bucket compile accounting, same swap protocol, one SPMD executable
per bucket. The sharded path is XLA-only and float-only (Pallas and
W8A8 are typed refusals); prompts at or past ``ring_prefill_min`` prefill
through the sequence-parallel ring-attention twin instead of the
blocked path (allclose-, not bit-, equivalent — decode from ring KV is
still the blocked bit-exact program).

Buckets:
- prefill: prompt length padded to pow2 (``("llmp", S)`` in the
  compile-cache manifest — replayed by ``warm_start`` so a restarted
  server compiles its prompt working set off the hot path);
- decode: active-row count padded to pow2 (``("llmd", B)``), padding
  rows write to the scratch block;
- chunk: one fixed prompt-chunk bucket (``("llmp_chunk", C)``) — every
  chunk of a chunked prefill, including the short final one, pads to
  the same bucket so the whole family is one executable.

Greedy ids stay on the device (llm/next_ids.py): a decode launched with
``sync=False`` leaves each row's `argmax` in ``last_ids``, where the
next launch finds it, and ``resolve`` reads `rows x 4` bytes a step
later. The three small programs that do it are first called where their
bucket's program is first built (one shape a decode bucket; the
prefills' one shape in all), so a bucket's first call holds theirs and
`compile_count` stays a count of buckets.

Which programs a model is served by, with which arguments, refusing what
and counted how is one object's to say: the family's program set
(`llm/families.py`, chosen once at construction from the bundle's
`LMSpec.family`). This file asks it and names no family: it keeps the
buckets, the jit cache and its keys, versions and hot swap, warm-up,
`last_ids`, launch and resolve, spans and the `devprof` hooks.

Kernel selection (``paged_kernel`` prop / ``NNS_PAGED_KERNEL`` env,
default ``xla``): the attention inner loop is either the XLA reference
(`llm/paged_model.py` — the bit-parity path against
`transformer.generate`) or the paged Pallas flash kernels
(`backends/pallas_paged.py`). The kernel is part of the jit key and
invocations are counted per kernel. The kernel that was asked for is
the kernel that runs: a Pallas kernel the compiler refuses raises
`BackendError` from the call that needed it, and `paged_kernel=pallas`
with `shards>0` is refused at construction. ``paged_kernel`` chooses
who reads the pool through the block table, and nothing else: what a
family does with a tile once XLA has gathered it is the family's own
(the sparse-expert chunk updates its attention's carry in a Pallas
kernel on a TPU under ``paged_kernel=xla``, chosen by
`llm/sparse_moe.fused_attend` from backend and shapes, and says which
on its spans: `attend`).

Weights are passed as jit *arguments* (not closed over), so a same-
shape hot swap is served by the already-compiled executable — the
version namespace exists for accounting and for swaps that change
widths, which compile fresh under their own keys.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from nnstreamer_tpu.core.errors import BackendError
from nnstreamer_tpu.core.log import get_logger
from nnstreamer_tpu.llm.families import program_set
from nnstreamer_tpu.llm.paged_cache import SCRATCH_BLOCK, PagedKVCache
from nnstreamer_tpu.runtime import devprof
from nnstreamer_tpu.runtime.sync import device_sync
from nnstreamer_tpu.runtime.tracing import NULL_TRACER

log = get_logger("backends.llm")

#: jax.monitoring duration events of a first call -> the label of the
#: child span they become under the call's `compile` span
_FIRST_CALL_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower",
    "/jax/core/compile/backend_compile_duration": "jax_backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "jax_cache_retrieval",
}


@dataclass
class DecodeLaunch:
    """A decode step launched with ``sync=False``, until `resolve` reads
    it: the rows' ids and what the program returned beside its logits
    (llm/families.py), still on the device, and what its `invoke` span
    says (None for a first call, whose `compile` span is already
    written)."""

    ids: Any
    beside: tuple
    rows: int
    t0: float
    span: Optional[dict]


def _model_dims(params: dict, n_heads: int, spec=None) -> dict:
    """Model dims: from the bundle's own description (`ModelBundle.lm`,
    llm/spec.LMSpec) when it has one, else from the params' shapes and
    the element's `n_heads`, as a dense bundle always was read."""
    if spec is None:
        return _derive_dims(params, n_heads)
    try:
        return {"d_model": int(params["embed"].shape[1]),
                "vocab": int(params["head"].shape[1]),
                "n_layers": len(params["blocks"]),
                "head_dim": int(spec.head_dim), "n_kv": int(spec.n_kv)}
    except (KeyError, IndexError, AttributeError, TypeError) as e:
        raise BackendError(
            f"tensor_llm needs an embed/blocks/ln_f/head params pytree; "
            f"could not read dims: {e}") from e


def _derive_dims(params: dict, n_heads: int) -> dict:
    """Model dims from the transformer params pytree itself (the only
    honest source — a store version may differ from element props)."""
    try:
        d_model = int(params["embed"].shape[1])
        vocab = int(params["head"].shape[1])
        n_layers = len(params["blocks"])
        hd = d_model // n_heads
        n_kv = (int(params["blocks"][0]["wqkv"].shape[1]) - d_model) \
            // 2 // hd
    except (KeyError, IndexError, AttributeError, TypeError) as e:
        raise BackendError(
            f"tensor_llm needs transformer-family params "
            f"(embed/blocks/ln_f/head pytree, models/transformer.py); "
            f"could not read dims: {e}") from e
    if hd * n_heads != d_model:
        raise BackendError(
            f"n_heads={n_heads} does not divide d_model={d_model}")
    return {"d_model": d_model, "vocab": vocab, "n_layers": n_layers,
            "head_dim": hd, "n_kv": n_kv}


class PagedLLMExecutor:
    """Device executor for the continuous-batching engine.

    `model` is a ``store://name[@version]`` ref (tracked or pinned, zoo
    builtins seed as @0) or a raw transformer params dict. One instance
    per engine; all methods run on the engine's single scheduler
    thread.
    """

    def __init__(self, model="store://transformer", *, n_heads: int = 4,
                 dtype=None, block_size: int = 16, num_blocks: int = 64,
                 max_len: int = 128, paged_kernel: Optional[str] = None,
                 shards: int = 0, shard_chips=None,
                 ring_prefill_min: int = 0, state_slots: int = 0,
                 prefill_chunk: int = 0, tracer=NULL_TRACER,
                 name: str = "llm"):
        import jax.numpy as jnp

        self.name = name
        self.tracer = tracer
        self.shards = int(shards)
        self.ring_prefill_min = int(ring_prefill_min)
        self.kernel_invokes: Dict[str, int] = {"pallas": 0, "xla": 0}
        kern = (paged_kernel or os.environ.get("NNS_PAGED_KERNEL")
                or "xla").strip().lower()
        if kern not in ("pallas", "xla"):
            raise BackendError(
                f"paged_kernel must be 'pallas' or 'xla', got {kern!r}")
        if kern == "pallas" and self.shards > 0:
            raise BackendError(
                f"llm {name}: paged_kernel=pallas is single-chip and "
                f"shards={self.shards} serves on the sharded XLA path; "
                f"drop one of the two")
        self.paged_kernel = kern
        self.n_heads = int(n_heads)
        self.dtype = jnp.dtype(dtype) if dtype is not None \
            else jnp.float32
        self.max_len = int(max_len)
        self._entry = None
        self._pinned: Optional[int] = None
        self._version: Optional[int] = None
        self.adopted_epoch = -1
        self.swap_count = 0
        if isinstance(model, str):
            from nnstreamer_tpu.serving.store import (
                get_store, parse_store_ref)

            if model.startswith("zoo://"):
                model = "store://" + model[len("zoo://"):]
            ref = parse_store_ref(model)
            self._entry = get_store().entry(ref.name)
            if ref.version is not None:
                self._pinned = self._entry.resolve_version(ref.version)
                self._version = self._pinned
            else:
                cur, epoch = self._entry.state
                self._version, self.adopted_epoch = cur, epoch
            bundle = self._entry.bundle(self._version)
            self.params, self.spec = bundle.params, bundle.lm
            self._entry.attach(self)
        elif isinstance(model, dict):
            self.params, self.spec = model, None
        elif hasattr(model, "params") and hasattr(model, "lm"):
            # a ModelBundle in hand: its params and its description
            self.params, self.spec = model.params, model.lm
        else:
            raise BackendError(
                f"tensor_llm model must be a store:// ref, a ModelBundle "
                f"or a params dict, got {type(model).__name__}")
        if self.spec is not None:
            self.n_heads = int(self.spec.n_heads)
        dims = _model_dims(self.params, self.n_heads, self.spec)
        self.__dict__.update(dims)
        bs = int(block_size)
        self.max_blocks = max(1, -(-self.max_len // bs))
        #: the family's programs, layouts, refusals and counters, chosen
        #: once (llm/families.py); refuses here what it cannot serve
        self.programs = program_set(
            self.spec, name=name, params=self.params, dtype=self.dtype,
            n_heads=self.n_heads, n_kv=self.n_kv, head_dim=self.head_dim,
            block_size=bs, max_blocks=self.max_blocks, kernel=kern,
            shards=self.shards, shard_fns=self._shard_fns,
            # what a family sizes its own pools by: the engine's rows
            # (`state_slots`) and its prompt chunk
            rows=int(state_slots), chunk=int(prefill_chunk))
        self._mesh = None
        self._shard_chips: tuple = ()
        self._sparams: Dict[Any, Any] = {}   # vkey → blocked+placed tree
        self._rparams: Dict[Any, Any] = {}   # vkey → replicated raw (ring)
        self._sspecs = None
        self._sfns = None
        placer = None
        if self.shards:
            from nnstreamer_tpu.serving import sharding as shg

            shg.validate_shards(self.shards)
            chips = tuple(int(c) for c in shard_chips) \
                if shard_chips is not None else tuple(range(self.shards))
            if len(chips) != self.shards:
                raise BackendError(
                    f"llm {name}: shards={self.shards} but {len(chips)} "
                    f"chips leased: {chips}")
            self._shard_chips = chips
            self._shard_devs = shg.shard_devices(chips)
            self._mesh = shg.tp_mesh(self._shard_devs)
            # raises the typed float-only / 8-divisibility errors up
            # front, before any pool or jit exists
            placed, self._sspecs = shg.shard_llm_params(
                self.params, self._mesh, n_heads=self.n_heads)
            self._sparams[self._vkey()] = placed
            placer = shg.kv_pool_placer(self._mesh)
        # `state_slots`: sequences that may hold a state at once, where
        # the family keeps one a sequence (the engine passes max_batch)
        self.cache = PagedKVCache(
            num_blocks=int(num_blocks), block_size=bs,
            # the width of a row of the `k` pool is the family's to say:
            # a head's, or the latent's where no head has a row
            head_dim=self.programs.head_dim, idx_dim=self.programs.idx_dim,
            dtype=self.dtype, placer=placer, state_slots=int(state_slots),
            **self.programs.cache_kw(self.n_layers))
        #: bytes of a value in the pools, which keep K, V and the indexer's
        #: keys in the type they are computed in (llm/paged_cache.py)
        self.kv_pool_itemsize = int(self.cache.dtype.itemsize)
        #: each live sequence's last token, on the device, at the index
        #: of its table's first block (llm/next_ids.py); single-chip only
        self.last_ids = None if self.shards else jnp.zeros(
            (self.cache.num_blocks,), jnp.int32)
        #: (ns, kind, bucket) → jitted callable
        self._jits: Dict[tuple, Any] = {}
        self.compile_count = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.prefills = 0
        self.chunk_prefills = 0
        self.decode_steps = 0
        #: chunks launched with sync=False whose values beside the
        #: logits are still on the device: (req, pos0, clen, values)
        self._chunk_beside: List[tuple] = []
        # first-call anatomy: a list from a jit miss (_get_jit) to its
        # `compile` span, holding jax's own duration events in between
        # as (label, t0, t1). Listened to only by a traced executor.
        self._first_call: Optional[list] = None
        self._listening = bool(tracer.active)
        if self._listening:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                self._on_jax_duration)

    # -- store integration -------------------------------------------------
    def _vkey(self, version: Optional[int] = None):
        """Version key for the sharded param caches: the explicit
        version, else the bound one, else 0 for raw-dict models."""
        if version is not None:
            return version
        return self._version if self._entry is not None else 0

    def _ns(self, version: Optional[int] = None) -> tuple:
        if self.shards:
            return ("tp", self.shards, self._vkey(version))
        if self._entry is not None:
            return ("v", version if version is not None
                    else self._version)
        return ("g", 0)

    # -- sharded serving (serving/sharding.py) -----------------------------
    def _shard_fns(self):
        if self._sfns is None:
            from nnstreamer_tpu.serving import sharding as shg

            self._sfns = shg.make_llm_fns(self._mesh, self._sspecs,
                                          self._shard_devs)
        return self._sfns

    def _raw_params(self, vkey):
        if self._entry is not None and vkey != self._version:
            return self._entry.bundle(vkey).params
        return self.params

    def _exec_params(self, kind: str = "prefill", version=None):
        """The params tree one jit call serves: single-chip, the raw
        host tree; sharded, the canonically-blocked head-sharded tree
        for the version (ring prefill: the replicated raw tree), placed
        once per version and cached — a hot-path call is a dict hit."""
        if not self.shards:
            return self.params
        from nnstreamer_tpu.serving import sharding as shg

        vkey = self._vkey(version)
        if kind == "ring":
            if vkey not in self._rparams:
                self._rparams[vkey] = shg.replicate_params(
                    self._raw_params(vkey), self._mesh)
            return self._rparams[vkey]
        if vkey not in self._sparams:
            self._sparams[vkey], _ = shg.shard_llm_params(
                self._raw_params(vkey), self._mesh, n_heads=self.n_heads)
        return self._sparams[vkey]

    @property
    def tracks_store_epoch(self) -> bool:
        return self._entry is not None and self._pinned is None

    def swap_due(self) -> bool:
        """Whether the next `maybe_adopt` will adopt a flipped epoch."""
        return self.tracks_store_epoch \
            and self._entry.state[1] != self.adopted_epoch

    def maybe_adopt(self) -> None:
        """Adopt a flipped store epoch at a step boundary. In-flight
        sequences keep their old-version KV and, where the family keeps
        one, their old-version state in their slot (documented serving
        tradeoff, docs/llm_serving.md) — retiring them instead would
        turn every swap into a latency spike for every live request."""
        if not self.swap_due():
            return
        cur, epoch = self._entry.state        # one read = consistent
        old = self._version
        bundle = self._entry.bundle(cur)
        dims = _model_dims(bundle.params, self.n_heads, bundle.lm)
        if dims["n_layers"] != self.n_layers or dims["n_kv"] != self.n_kv \
                or dims["head_dim"] != self.head_dim \
                or bundle.lm != self.spec:
            # pool-incompatible geometry cannot serve in-flight
            # sequences; refuse the adoption loudly rather than corrupt
            raise BackendError(
                f"store swap {self._entry.name}@{old} → @{cur} changes "
                f"cache geometry (layers/kv-heads/head-dim) or the "
                f"model's description; restart the tensor_llm element "
                f"to serve it")
        self.params = bundle.params
        self.__dict__.update(dims)
        keep = {cur, self._pinned}
        if self.shards:
            for k in [k for k in self._jits
                      if k[0][0] == "tp" and k[0][2] not in keep]:
                del self._jits[k]
            self._sparams = {v: p for v, p in self._sparams.items()
                             if v in keep}
            self._rparams = {v: p for v, p in self._rparams.items()
                             if v in keep}
            # place cur now if the swap controller's prewarm missed us
            self._exec_params("prefill", cur)
        else:
            for k in [k for k in self._jits
                      if k[0][0] == "v" and k[0][1] not in keep]:
                del self._jits[k]
        self._version, self.adopted_epoch = cur, epoch
        self.swap_count += 1
        self.tracer.record_swap(
            self.name, time.perf_counter(), model=self._entry.name,
            from_version=old, to_version=cur, epoch=epoch,
            prewarmed=True)
        log.info("llm %s adopted %s@%d epoch=%d", self.name,
                 self._entry.name, cur, epoch)

    def _note_bucket(self, bucket_key: tuple) -> None:
        if self._entry is not None and self._version is not None:
            self._entry.note_bucket(self._version, bucket_key)

    # -- jit cache ---------------------------------------------------------
    def _get_jit(self, kind: str, bucket: int, version=None):
        import jax

        key = (self._ns(version), kind, bucket, self.programs.kernel(kind))
        jitted = self._jits.get(key)
        if jitted is not None:
            self.cache_hits += 1
            return jitted, False
        self.cache_misses += 1
        prog = self.programs.program(kind)
        self._warm_ids(kind, bucket)
        self._first_call = []
        jitted = jax.jit(prog.fn, static_argnames=prog.static,
                         donate_argnums=prog.donate)
        self._jits[key] = jitted
        return jitted, True

    def _warm_ids(self, kind: str, bucket: int) -> None:
        """First call of the id programs (llm/next_ids.py) that serve
        beside a bucket's program, made where that program is first
        built, so that a bucket's first call holds theirs: the two of a
        decode bucket, or the one every prefill shares. Every write goes
        to the scratch block's entry."""
        if self.shards:
            return
        import jax.numpy as jnp

        from nnstreamer_tpu.llm import next_ids

        if kind != "decode":
            _, self.last_ids = next_ids.llm_pick_first(
                jnp.zeros((self.vocab,), jnp.float32), self.last_ids,
                np.int32(SCRATCH_BLOCK))
            return
        tab = jnp.full((bucket, self.max_blocks), SCRATCH_BLOCK, jnp.int32)
        next_ids.llm_last_ids(self.last_ids, tab,
                              np.zeros((bucket,), np.int32))
        _, self.last_ids = next_ids.llm_pick_rows(
            jnp.zeros((bucket, self.vocab), jnp.float32), self.last_ids,
            tab)

    def _run_kernel(self, kind: str, run):
        """Call `run()` (get the bucket's jit and invoke it). On the
        Pallas path a failure is the kernel's: the compiler refused it
        or the program faulted, and the caller asked for that kernel —
        raise it typed, naming the kind, never serve another kernel in
        its place."""
        if self.programs.kernel(kind) != "pallas":
            return run()
        try:
            return run()
        except Exception as e:
            raise BackendError(
                f"llm {self.name}: paged_kernel=pallas {kind} failed "
                f"({type(e).__name__}: {e}); set paged_kernel=xla to "
                f"serve on the XLA reference") from e

    def _span(self, kind: str, t0: float, t1: float, **args) -> None:
        if kind == "compile":
            self._first_call_children()
        if self.tracer.active:
            self.tracer.backend_span(self.name, kind, t0, t1, **args)

    def _on_jax_duration(self, event: str, duration: float, **_) -> None:
        label = _FIRST_CALL_EVENTS.get(event)
        if label is not None and self._first_call is not None:
            t = time.perf_counter()
            self._first_call.append((label, t - duration, t))

    def _first_call_children(self) -> None:
        """The first call just made, as jax timed it: children of its
        `compile` span, the outermost event of each label only (tracing
        reports every nested jit, hundreds a layer). Events arrive in
        the order they end, so one that starts no earlier than a later
        one of its label lies inside it."""
        events, self._first_call = self._first_call or (), None
        first: Dict[str, float] = {}
        for label, t0, t1 in reversed(events):
            if t0 < first.get(label, float("inf")):
                first[label] = t0
                self.tracer.span("backend", self.name, label, t0, t1)

    def _resolve(self, dev, beside: tuple, sync: bool, kind: str,
                 bucket: int, t_in: float, t0: float):
        """The end of a call whose jit has just returned: with `sync`,
        wait for `dev` and for what the program returned `beside` it,
        and read them back (the chunks launched before the call are
        done too). An active tracer gets the call's children in order,
        disjoint: `prep` [t_in, t0) (building the host arrays),
        `dispatch` (t0 to the jit's return), `wait` (the device_sync
        alone) and `readback` (the np.asarray) — the last three divide
        the enclosing invoke/compile span [t0, t1) into launch, device
        wait and D2H. Returns (result, host `beside` or None, t1)."""
        tr = self.tracer
        on = tr.active
        t_d = t_w = time.perf_counter() if on else 0.0
        out, host = dev, None
        if sync:
            device_sync((dev, *beside), tracer=tr,
                        name=f"{self.name}:{kind}")
            if on:
                t_w = time.perf_counter()
            out = np.asarray(dev)  # nnlint: disable=NNL002 synced by the device_sync above; timed apart from it as readback
            host = [np.asarray(d) for d in beside]  # nnlint: disable=NNL002 synced by the device_sync above
            nbytes = int(out.nbytes) + sum(int(h.nbytes) for h in host)
        t1 = time.perf_counter()
        if on:
            what = f"llm_{kind}"
            tr.span("backend", self.name, "prep", t_in, t0, what=what)
            tr.span("backend", self.name, "dispatch", t0, t_d, what=what,
                    bucket=bucket)
            if sync:
                tr.span("backend", self.name, "wait", t_d, t_w, what=what)
                tr.span("backend", self.name, "readback", t_w, t1,
                        what=what, bytes=nbytes)
        if host:
            self._drain_chunks(wait=True)
        return out, host, t1

    def _drain_chunks(self, wait: bool = False) -> None:
        """Read back what chunks launched with sync=False returned
        beside their logits, once the device has it (all of it after a
        sync: the device runs in order), hand it to the family's
        accounting and put what that says on a `resolve` span under the
        chunk's own `req` and `clen` and what its `invoke` said of where
        it starts (`pos0` and the family's `note_chunk`)."""
        while self._chunk_beside:
            req, clen, bucket, extra, dev = self._chunk_beside[0]
            if not (wait or all(d.is_ready() for d in dev)):
                return
            self._chunk_beside.pop(0)
            t0 = time.perf_counter()
            host = [np.asarray(d) for d in dev]  # nnlint: disable=NNL002 ready, or behind the caller's device_sync
            said = self.programs.note_beside("chunk", host, bucket)
            if self.tracer.active:
                self.tracer.span(
                    "backend", self.name, "resolve", t0,
                    time.perf_counter(), what="llm_prefill_chunk",
                    req=req, clen=clen, **extra, **said)

    # -- device performance plane (runtime/devprof.py) ---------------------
    def resident_bytes(self) -> int:
        """Device bytes this executor pins: params + the paged KV pool
        — the executor-level HBM attribution row."""
        import jax

        if self.shards:
            # device-resident = the placed trees (blocked + any ring
            # replicas, every cached version), not the raw host pytree
            n = sum(
                getattr(a, "nbytes", 0)
                for tree in list(self._sparams.values())
                + list(self._rparams.values())
                for a in jax.tree_util.tree_leaves(tree))
        else:
            n = sum(getattr(a, "nbytes", 0)
                    for a in jax.tree_util.tree_leaves(self.params))
        return n + self.cache.resident_bytes()

    def _prof_capture(self, bucket: str, jitted, args: tuple,
                      kwargs: dict, seconds: float) -> None:
        """Compile-event capture: cost-model read on the freshly
        compiled bucket (re-lower only; compile misses are rare by
        construction — prewarm_buckets exists to make them zero)."""
        prof = devprof.get()
        if not prof.enabled:
            return
        prof.attach_model(self.name, self)
        prof.capture_cost(self.name, bucket, jitted, args,
                          kwargs=kwargs, seconds=seconds)

    # -- prefill -----------------------------------------------------------
    def prefill(self, prompt: np.ndarray, block_table: List[int],
                *, sync: bool = True, req: Optional[str] = None,
                state_slot: Optional[int] = None,
                window_table: Optional[List[int]] = None):
        """One whole prompt; its KV lands in the pool blocks of
        `block_table`. Dispatches between the full-sequence
        `apply_seq_kv` path and the chunk family (the program set's
        `prefill_kind` — pallas / quantized stores go through the chunk
        path, as one chunk covering the prompt). Returns last-token
        logits: a host (vocab,) f32 array when `sync`, else the device
        array so the engine can batch one `device_sync` over a whole
        step's admissions. `req` only labels the call's `invoke` span;
        `state_slot` is the sequence's slot of the state pool, where the
        family keeps a state a sequence; `window_table` its table of the
        window layers' pools, where it keeps those."""
        from nnstreamer_tpu.backends.xla import next_pow2

        t_in = time.perf_counter() if self.tracer.active else 0.0
        plen = int(prompt.shape[0])
        ps = self.programs
        if ps.prefill_kind(self.params) == "chunk":
            ps.check_prompt(plen, 0)
            return self.prefill_chunk(
                prompt, 0, block_table, bucket=next_pow2(plen, 8),
                sync=sync, req=req, state_slot=state_slot,
                window_table=window_table)
        kind = "prefill"
        if self.shards and 0 < self.ring_prefill_min <= plen:
            kind = "ring"    # sequence-parallel long-context cutover
        s_b = next_pow2(plen, 8)
        bs = self.cache.block_size
        ids = np.zeros((1, s_b), np.int32)
        ids[0, :plen] = prompt
        blk_idx = np.full((s_b,), SCRATCH_BLOCK, np.int32)
        pos = np.arange(plen)
        blk_idx[:plen] = np.asarray(block_table, np.int32)[pos // bs]
        blk_off = (np.arange(s_b) % bs).astype(np.int32)
        jitted, fresh = self._get_jit(kind, s_b)
        args = (self._exec_params(kind), ids, blk_idx, blk_off,
                np.int32(plen - 1))
        prof = devprof.get()
        if prof.enabled:
            prof.note_dispatch(self.name, f"{kind}:{s_b}")
        t0 = time.perf_counter()
        logits, _, pools = ps.split(jitted(
            *ps.prefill_args(*args, self.cache.pools()), **ps.kw))
        self.cache.set_pools(pools)
        out, _, t1 = self._resolve(logits, (), sync, "prefill", s_b, t_in,
                                   t0)
        kernel = "ring" if kind == "ring" else "xla"
        if fresh:
            self.compile_count += 1
            self._span("compile", t0, t1, what="llm_prefill", bucket=s_b,
                       kernel=kernel)
            self._note_bucket(
                ("llmr" if kind == "ring" else "llmp", s_b))
            self._prof_capture(
                f"{kind}:{s_b}", jitted,
                ps.prefill_args(*args, self.cache.pools()), ps.kw, t1 - t0)
        else:
            self._span("invoke", t0, t1, what="llm_prefill", bucket=s_b,
                       plen=plen, kernel=kernel, req=req)
        self.prefills += 1
        self.kernel_invokes["xla"] += 1
        return out

    def prefill_chunk(self, chunk: np.ndarray, pos0: int,
                      block_table: List[int], *, bucket: int = 0,
                      sync: bool = True, req: Optional[str] = None,
                      state_slot: Optional[int] = None,
                      window_table: Optional[List[int]] = None):
        """One prompt chunk starting at absolute position `pos0`,
        scattered into `block_table`'s blocks and attending the whole
        prefix written so far. `bucket` pins the pad width so every
        chunk of a prompt (the short final one included) hits one
        executable; 0 = pow2 of this chunk. Returns the chunk's
        last-token logits (host when `sync`, device otherwise) — only
        the final chunk's value is meaningful to sampling. A family
        with window pools writes the chunk through `window_table` too,
        whose entries behind the window read the scratch block."""
        from nnstreamer_tpu.backends.xla import next_pow2

        t_in = time.perf_counter() if self.tracer.active else 0.0
        clen = int(chunk.shape[0])
        c_b = max(int(bucket) or 0, next_pow2(clen, 8))
        bs = self.cache.block_size
        ids = np.zeros((1, c_b), np.int32)
        ids[0, :clen] = chunk
        blk_idx = np.full((c_b,), SCRATCH_BLOCK, np.int32)
        pos = int(pos0) + np.arange(clen)
        blk_idx[:clen] = np.asarray(block_table, np.int32)[pos // bs]
        blk_off = ((int(pos0) + np.arange(c_b)) % bs).astype(np.int32)
        tab = np.full((self.max_blocks,), SCRATCH_BLOCK, np.int32)
        tab[:len(block_table)] = block_table
        args = (self.params, ids, np.int32(pos0), blk_idx, blk_off, tab,
                np.int32(clen - 1))
        ps = self.programs
        kw = ps.chunk_kw(pos0, c_b)
        slot = np.int32(state_slot or 0)        # none: the scratch slot
        window = None
        if self.cache.window_alloc is not None:
            # the same layout through the window layers' table
            wtab = np.full((self.max_blocks,), SCRATCH_BLOCK, np.int32)
            wtab[:len(window_table)] = window_table
            wblk_idx = np.full((c_b,), SCRATCH_BLOCK, np.int32)
            wblk_idx[:clen] = wtab[pos // bs]
            window = (wblk_idx, wtab)

        def _run():
            jitted, fresh = self._get_jit("chunk", c_b)
            logits, beside, pools = ps.split(jitted(
                *ps.chunk_args(*args, self.cache.pools(), slot, window),
                **kw))
            self.cache.set_pools(pools)
            return logits, beside, fresh

        prof = devprof.get()
        if prof.enabled:
            prof.note_dispatch(self.name, f"chunk:{c_b}")
        t0 = time.perf_counter()
        logits, beside, fresh = self._run_kernel("chunk", _run)
        kernel = ps.kernel("chunk")
        out, host, t1 = self._resolve(
            logits, beside, sync, "prefill_chunk", c_b, t_in, t0)
        # what the family counts of the chunk from where it starts
        extra = ps.note_chunk(int(pos0), clen, c_b)
        if beside:
            # the span also says where the chunk starts and, once what
            # came beside the logits is on the host, what the family
            # reads from it; until then a `resolve` span will
            extra["pos0"] = int(pos0)
            if host:
                extra.update(ps.note_beside("chunk", host, c_b))
            else:
                self._drain_chunks()
                self._chunk_beside.append((req, clen, c_b, extra, beside))
        if fresh:
            self.compile_count += 1
            self._span("compile", t0, t1, what="llm_prefill_chunk",
                       bucket=c_b, kernel=kernel)
            self._note_bucket(("llmp_chunk", c_b))
            jitted, _ = self._get_jit("chunk", c_b)
            self._prof_capture(
                f"chunk:{c_b}", jitted,
                ps.chunk_args(*args, self.cache.pools(), slot, window), kw,
                t1 - t0)
        else:
            self._span("invoke", t0, t1, what="llm_prefill_chunk",
                       bucket=c_b, clen=clen, kernel=kernel, req=req,
                       **extra)
        self.chunk_prefills += 1
        self.kernel_invokes[kernel] += 1
        return out

    # -- decode ------------------------------------------------------------
    def decode(self, cur: List[Optional[int]], tables: List[List[int]],
               pos: List[int], *, sync: bool = True,
               state_slots: Optional[List[int]] = None,
               window_tables: Optional[List[List[int]]] = None):
        """One decode step for `len(cur)` live rows (bucketed to pow2;
        padding rows write to the scratch block). `cur[i]` is row i's
        last token, or None where the host has not read it: the step
        then takes it from `last_ids`, where the row's unsynced launch
        or its prefill's `pick_first` left it. With `sync` (default)
        returns host logits (n, vocab) f32 for the live rows. With
        sync=False nothing is waited for: the rows' greedy ids are
        taken on the device, kept in `last_ids` for the next launch, and
        the returned `DecodeLaunch` is read by `resolve` (single-chip
        only). `state_slots[i]` is row i's slot of the state pool, where
        the family keeps a state a sequence (padding rows take the
        scratch slot); `window_tables[i]` its table of the window
        layers' pools, where it keeps those."""
        import jax

        from nnstreamer_tpu.backends.xla import next_pow2
        from nnstreamer_tpu.llm import next_ids

        if self.shards and not sync:
            raise BackendError(
                f"llm {self.name}: an unsynced decode keeps its ids on "
                f"one chip; shards={self.shards} decodes with sync=True")
        t_in = time.perf_counter() if self.tracer.active else 0.0
        n = len(cur)
        b_b = next_pow2(n, 1)
        cur_a = np.zeros((b_b,), np.int32)
        # -1: on the device, at the first block of the row's table
        cur_a[:n] = [-1 if c is None else c for c in cur]
        tab_a = np.full((b_b, self.max_blocks), SCRATCH_BLOCK, np.int32)
        for i, t in enumerate(tables):
            tab_a[i, :len(t)] = t
        pos_a = np.zeros((b_b,), np.int32)
        pos_a[:n] = pos
        slot_a = None
        if state_slots is not None:
            slot_a = np.zeros((b_b,), np.int32)
            slot_a[:n] = state_slots
        wtab_a = None
        if window_tables is not None:
            wtab_a = np.full((b_b, self.max_blocks), SCRATCH_BLOCK, np.int32)
            for i, t in enumerate(window_tables):
                wtab_a[i, :len(t)] = t
        ps = self.programs

        def _run():
            jitted, fresh = self._get_jit("decode", b_b)
            cur_d, tab_d, ids = cur_a, tab_a, None
            if not self.shards:
                # the tables go up once for the step and the two id
                # programs beside it; `cur` is a device array whether
                # the ids came from the host or not: one kind of
                # argument, one executable a bucket
                tab_d = jax.device_put(tab_a)
                cur_d = next_ids.llm_last_ids(self.last_ids, tab_d, cur_a)
            logits, beside, pools = ps.split(jitted(*ps.decode_args(
                self._exec_params("decode"), cur_d, tab_d, pos_a, n,
                self.cache.pools(), slot_a, wtab_a), **ps.kw))
            self.cache.set_pools(pools)
            if not sync:
                ids, self.last_ids = next_ids.llm_pick_rows(
                    logits, self.last_ids, tab_d)
                # on their way while the next step is prepared
                for dev in (ids, *beside):
                    dev.copy_to_host_async()
            return logits, beside, ids, fresh

        prof = devprof.get()
        if prof.enabled:
            prof.note_dispatch(self.name, f"decode:{b_b}")
        t0 = time.perf_counter()
        logits, beside, ids, fresh = self._run_kernel("decode", _run)
        kernel = ps.kernel("decode")
        out, host, t1 = self._resolve(
            logits, beside, sync, "decode", b_b, t_in, t0)
        # the family's count of what the step attended and read
        # (kv_tokens, kv_slots, ...) and, where it is on the host, of
        # what the step returned beside its logits; a slot of kv_slots
        # is n_kv x head_dim values of kv_pool_itemsize bytes, K and V
        span = dict(what="llm_decode", bucket=b_b, rows=n, kernel=kernel,
                    kv_pool_itemsize=self.kv_pool_itemsize,
                    **ps.note_decode(pos_a, n))
        if host:
            span.update(ps.note_beside("decode", host, b_b))
        if fresh:
            self.compile_count += 1
            self._span("compile", t0, t1, what="llm_decode", bucket=b_b,
                       kernel=kernel)
            self._note_bucket(("llmd", b_b))
            jitted, _ = self._get_jit("decode", b_b)
            self._prof_capture(
                f"decode:{b_b}", jitted, ps.decode_args(
                    self._exec_params("decode"), cur_a, tab_a, pos_a, n,
                    self.cache.pools(), slot_a, wtab_a), ps.kw, t1 - t0)
        elif sync:
            self._span("invoke", t0, t1, **span)
        self.decode_steps += 1
        self.kernel_invokes[kernel] += 1
        if sync:
            return out[:n]
        return DecodeLaunch(ids, beside, n, t0, None if fresh else span)

    def pick_first(self, logits, block_table: List[int]):
        """The greedy first token of a prefill launched with sync=False,
        taken on the device from its `logits` and kept in `last_ids` for
        the sequence's first decode launch. Returns the device id, which
        `resolve` reads back."""
        from nnstreamer_tpu.llm import next_ids

        on = self.tracer.active
        t0 = time.perf_counter() if on else 0.0
        tok, self.last_ids = next_ids.llm_pick_first(
            logits, self.last_ids, np.int32(block_table[0]))
        tok.copy_to_host_async()
        if on:
            self.tracer.span("backend", self.name, "dispatch", t0,
                             time.perf_counter(), what="llm_first")
        return tok

    def resolve(self, launch: Optional[DecodeLaunch], firsts=()) -> tuple:
        """Wait for an unsynced decode launch (None: there was none) and
        for the first ids picked beside it, and read them back: one
        `device_sync`, one `wait` and one `readback` span. Returns (the
        launch's ids (rows,) int32 or None, the first ids). The launch's
        `invoke` span is written here, from the launch to the end of the
        wait, with what only the read-back tells: the family's reading
        of what the step returned beside its logits."""
        tr = self.tracer
        on = tr.active
        dev = list(firsts)
        if launch is not None:
            dev.append(launch.ids)
            dev.extend(launch.beside)
        t_w0 = time.perf_counter() if on else 0.0
        device_sync(dev, tracer=tr, name=f"{self.name}:decode")
        t_w = time.perf_counter() if on else 0.0
        host = [np.asarray(d) for d in dev]  # nnlint: disable=NNL002 synced by the device_sync above; timed apart from it as readback
        if on:
            tr.span("backend", self.name, "wait", t_w0, t_w,
                    what="llm_decode")
            tr.span("backend", self.name, "readback", t_w,
                    time.perf_counter(), what="llm_decode",
                    bytes=sum(int(h.nbytes) for h in host))
        nf = len(firsts)
        first_ids = [int(h) for h in host[:nf]]
        if launch is None:
            return None, first_ids
        span = launch.span
        if launch.beside:
            # the launch's ids are a row a row of its bucket
            said = self.programs.note_beside("decode", host[nf + 1:],
                                             len(host[nf]))
            # the chunks launched before it are done too
            self._drain_chunks()
            if span is not None:
                span.update(said)
        if span is not None:
            self._span("invoke", launch.t0, t_w, **span)
        return host[nf][:launch.rows], first_ids

    # -- warm paths --------------------------------------------------------
    def _warm_compile(self, kind: str, bucket: int, version=None,
                      params=None) -> bool:
        """Compile one bucket off the hot path by running the jit on
        DUMMY inputs whose every write targets the scratch block — by
        construction that corrupts nothing (scratch absorbs garbage by
        design), and unlike `.lower().compile()` a real invocation
        populates the jit's dispatch cache, so the first *served*
        request is a cache hit, not a second compile. Returns whether a
        fresh executable was built."""
        ps = self.programs
        key = (self._ns(version), kind, bucket, ps.kernel(kind))
        if key in self._jits:
            return False
        jitted, _ = self._get_jit(kind, bucket, version)
        if self.shards:
            # sharded jits only accept the placed (blocked / replicated)
            # tree for the version — never a caller-supplied raw tree
            params = self._exec_params(kind, version)
        else:
            params = self.params if params is None else params
        prof = devprof.get()
        if prof.enabled:
            prof.note_dispatch(self.name, f"{kind}:{bucket}")
        kw = ps.kw
        pools = self.cache.pools
        t0 = time.perf_counter()
        if kind == "decode":
            cur = pos = np.zeros((bucket,), np.int32)
            tab = np.full((bucket, self.max_blocks), SCRATCH_BLOCK,
                          np.int32)
            if not self.shards:
                # on the device, as `decode` passes them
                import jax

                from nnstreamer_tpu.llm import next_ids

                tab = jax.device_put(tab)
                cur = next_ids.llm_last_ids(self.last_ids, tab, cur)

            def layout():       # no live row, every state the scratch's
                return ps.decode_args(params, cur, tab, pos, 0, pools(),
                                      np.zeros((bucket,), np.int32), tab)
        else:
            ids = np.zeros((1, bucket), np.int32)
            blk = np.full((bucket,), SCRATCH_BLOCK, np.int32)
            off = (np.arange(bucket)
                   % self.cache.block_size).astype(np.int32)
            zero = np.int32(0)
            if kind == "chunk":
                kw = ps.chunk_kw(0, bucket)
                tab = np.full((self.max_blocks,), SCRATCH_BLOCK, np.int32)

                def layout():
                    return ps.chunk_args(params, ids, zero, blk, off, tab,
                                         zero, pools(), zero, (blk, tab))
            else:
                def layout():
                    return ps.prefill_args(params, ids, blk, off, zero,
                                           pools())
        logits, _, kept = ps.split(jitted(*layout(), **kw))
        self.cache.set_pools(kept)
        device_sync(logits, tracer=self.tracer,
                    name=f"{self.name}:warm_{kind}")
        self.compile_count += 1
        t1 = time.perf_counter()
        self._span("compile", t0, t1, what=f"llm_{kind}_warm",
                   bucket=bucket)
        self._prof_capture(f"{kind}:{bucket}", jitted, layout(), kw,
                           t1 - t0)
        return True

    def warm_decode(self, rows: int) -> bool:
        """Build the decode bucket that holds `rows` rows, unless it is
        built. Returns whether it was built now."""
        from nnstreamer_tpu.backends.xla import next_pow2

        return self._warm_compile("decode", next_pow2(rows, 1))

    def prewarm_buckets(self, *, max_batch: int, max_prompt: int,
                        chunk: int = 0) -> int:
        """Eagerly compile every bucket a serving run can hit: decode
        pow2 buckets up to `max_batch`, prefill pow2 buckets up to
        `max_prompt`, and — when the engine runs chunked prefill — the
        one chunk bucket. Start-time cost, zero hot-path compiles
        after."""
        from nnstreamer_tpu.backends.xla import next_pow2

        compiled = 0
        b, top_b = 1, next_pow2(max(1, max_batch), 1)
        while b <= top_b:
            compiled += int(self._warm_compile("decode", b))
            b *= 2
        if chunk > 0:
            compiled += int(self._warm_compile(
                "chunk", next_pow2(chunk, 8)))
        if self.programs.prefill_kind(self.params) == "chunk":
            # whole-prompt prefills route through the chunk family too
            s, top_s = 8, next_pow2(
                min(max(1, max_prompt), self.max_len), 8)
            while s <= top_s:
                compiled += int(self._warm_compile("chunk", s))
                s *= 2
            return compiled
        s, top_s = 8, next_pow2(
            min(max(1, max_prompt), self.max_len), 8)
        while s <= top_s:
            compiled += int(self._warm_compile("prefill", s))
            s *= 2
        if self.shards and self.ring_prefill_min > 0:
            # buckets a ring-cutover prompt can land in
            s = next_pow2(max(8, self.ring_prefill_min), 8)
            while s <= top_s:
                compiled += int(self._warm_compile("ring", s))
                s *= 2
        return compiled

    def warm_start(self) -> int:
        """Replay the persistent manifest's prefill/decode buckets for
        the bound version (element start(), off the hot path)."""
        if self._entry is None:
            return 0
        from nnstreamer_tpu.serving.compile_cache import manifest_buckets

        compiled = 0
        for bk in manifest_buckets(self._entry.name, self._version):
            try:
                if bk[0] == "llmp":
                    compiled += int(self._warm_compile("prefill", bk[1]))
                elif bk[0] == "llmd":
                    compiled += int(self._warm_compile("decode", bk[1]))
                elif bk[0] == "llmp_chunk" and not self.shards:
                    compiled += int(self._warm_compile("chunk", bk[1]))
                elif bk[0] == "llmr" and self.shards:
                    compiled += int(self._warm_compile("ring", bk[1]))
            except Exception as e:    # warm start is never a gate
                log.warning("llm warm_start bucket %s failed: %s", bk, e)
        return compiled

    def prewarm_version(self, version: int, bundle) -> int:
        """Swap-controller hook (serving/store.py update()): compile the
        incoming version's executables for every bucket this executor
        has served, before the epoch flips."""
        params = getattr(bundle, "params", bundle)
        spec = getattr(bundle, "lm", None)
        dims = _model_dims(params, self.n_heads, spec)
        if dims["n_layers"] != self.n_layers or dims["n_kv"] != self.n_kv \
                or dims["head_dim"] != self.head_dim or spec != self.spec:
            raise BackendError(
                f"incoming {self._entry.name}@{version} changes cache "
                f"geometry; tensor_llm cannot hot-swap it over live "
                f"paged state — swap aborted")
        if self.shards:
            # place the incoming version's blocked tree NOW, from the
            # bundle in hand — if blocking refuses it (quantized, bad
            # divisibility) the swap aborts before any epoch flips
            from nnstreamer_tpu.serving import sharding as shg

            self._sparams[version], _ = shg.shard_llm_params(
                params, self._mesh, n_heads=self.n_heads)
            if self.ring_prefill_min > 0:
                self._rparams[version] = shg.replicate_params(
                    params, self._mesh)
        served = sorted({(k[1], k[2]) for k in self._jits})
        compiled = 0
        for kind, bucket in served:
            if self._warm_compile(kind, bucket, version=version):
                compiled += 1
        return compiled

    def close(self) -> None:
        if self._entry is not None:
            try:
                self._entry.detach(self)
            except Exception:
                pass
        if self._listening:
            import jax.monitoring

            self._listening = False
            jax.monitoring.unregister_event_duration_listener(
                self._on_jax_duration)
        self._jits.clear()
        self._sparams.clear()
        self._rparams.clear()
        self._sfns = None

    def stats(self) -> dict:
        out = {
            "compile_count": self.compile_count,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "prefills": self.prefills,
            "chunk_prefills": self.chunk_prefills,
            "decode_steps": self.decode_steps,
            "swap_count": self.swap_count,
            "paged_kernel": self.paged_kernel,
            "kernel_invokes": dict(self.kernel_invokes),
            "kv_pool_itemsize": self.kv_pool_itemsize,
            **self.programs.stats(),
        }
        if self.shards:
            out["shards"] = self.shards
            out["shard_chips"] = list(self._shard_chips)
            out["ring_prefill_min"] = self.ring_prefill_min
        if self._entry is not None:
            out["store"] = f"{self._entry.name}@{self._version}"
        return out
