"""XLA backend — the one first-class NN engine (replaces the reference's
vendor subplugin zoo, SURVEY.md §2.3; the BASELINE.json north star).

A model is a jax-traceable callable ``fn(params, *inputs) -> outputs``
plus a params pytree. Sources of models:

- the in-repo model zoo (``model=zoo://mobilenet_v2``) — models/zoo.py
- a python path (``model=pkg.module:build``) whose callable returns a
  `ModelBundle` or is itself the traced function
- a `ModelBundle` passed programmatically to the element

TPU-first properties:
- **Fusion**: the tensor_transform chains adjacent to the filter are
  absorbed via `fuse()` and traced into the *same* jit computation, so
  normalization/typecast/argmax run on-device fused around the matmuls —
  zero extra HBM round-trips (north star: "fold tensor_transform into the
  same XLA computation").
- **Negotiation via tracing**: output specs come from `jax.eval_shape`
  (no device work at build time).
- **Async dispatch**: `invoke` returns device arrays without blocking; the
  scheduler's queues overlap host work with device execution. The D2H
  sync happens once, at a sink/decoder (TensorBuffer.to_host) — the
  anti-pattern this avoids is the reference's per-frame
  cudaDeviceSynchronize (tensor_filter_tensorrt.cc:239).
"""

from __future__ import annotations

import importlib
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from nnstreamer_tpu.backends.base import (
    ArrayTuple,
    ElementwiseFn,
    FilterBackend,
    register_backend,
)
from nnstreamer_tpu.core.errors import (
    BackendError, SegmentStageError, WindowBuildError)
from nnstreamer_tpu.core.log import get_logger
from nnstreamer_tpu.runtime import devprof
from nnstreamer_tpu.tensor.dtypes import DType
from nnstreamer_tpu.tensor.info import TensorInfo, TensorsSpec

log = get_logger("backend.xla")


@dataclass
class ModelBundle:
    """A loadable model: traced function + params + optional fixed specs."""

    fn: Callable[..., Any]            # fn(params, *inputs) -> output(s)
    params: Any = None
    in_spec: Optional[TensorsSpec] = None
    out_spec: Optional[TensorsSpec] = None
    name: str = ""
    #: optional host-side input stage applied before H2D staging — for
    #: inputs that are bytes-parsing, not tensor math (e.g. the GraphDef
    #: DecodeWav entry: RIFF header decode happens here, PCM samples
    #: enter the XLA program)
    host_pre: Optional[Callable[[tuple], tuple]] = None
    #: for a language model served by tensor_llm: its family and the
    #: sizes its params do not spell out (llm/spec.LMSpec); None = the
    #: dense family, dims read from the params' shapes
    lm: Any = None


@dataclass
class _SharedEntry:
    """One device-resident model shared across filter instances
    (shared-tensor-filter-key analog, tensor_filter_common.c:2911-3046).
    On TPU the point is HBM dedup: N filters on one model hold ONE copy
    of the device params; reload swaps the entry for all holders."""

    bundle: ModelBundle
    device_params: Any
    device: Any = None
    model_ref: Optional[str] = None   # str model= of the first holder
    holders: int = 0
    version: int = 0


_shared_models: Dict[str, _SharedEntry] = {}
_shared_lock = threading.Lock()


@dataclass
class _VState:
    """One store version resident in this backend: bundle + device
    params. Compiled buckets live in `_dyn_jits` under ("v", version)
    namespaced keys, so retiring a version is a key sweep."""

    version: int
    bundle: ModelBundle
    device_params: Any = None


def next_pow2(n: int, floor: int = 1) -> int:
    v = max(n, floor)
    return 1 << (v - 1).bit_length()


def _to_tuple(x) -> Tuple:
    if isinstance(x, tuple):
        return x
    if isinstance(x, list):
        return tuple(x)
    return (x,)


def _spec_from_shapes(shapes) -> TensorsSpec:
    infos = tuple(
        TensorInfo(shape=tuple(s.shape), dtype=DType.from_np(s.dtype))
        for s in shapes
    )
    return TensorsSpec(tensors=infos)


@register_backend("xla")
class XLABackend(FilterBackend):
    def __init__(self):
        self._bundle: Optional[ModelBundle] = None
        self._pre: Optional[ElementwiseFn] = None
        self._post: Optional[ElementwiseFn] = None
        self._post_aux = None
        self._jitted = None
        self._device = None
        self._device_params = None
        self._in_spec: Optional[TensorsSpec] = None
        self._out_spec: Optional[TensorsSpec] = None
        self._loader_opts: Dict[str, Any] = {}
        self._shared: Optional[_SharedEntry] = None
        self._shared_key: Optional[str] = None
        self._jitted_version = -1
        # flexible-shape invoke: bounded cache of per-bucket compilations
        self._dyn_jits: "OrderedDict[tuple, Any]" = OrderedDict()
        self._dyn_cache_max = 16
        self._batch_ok: Dict[tuple, bool] = {}   # batchability verdicts
        self._dynamic_spatial = False
        self.compile_count = 0   # traces, observable for bucketing tests
        # bucket-cache behavior (_bucket_jit), surfaced in stats() via
        # tensor_filter.extra_stats and in backend trace spans
        self.cache_hits = 0
        self.cache_misses = 0
        # host→device staging accounting (zero-redundant-staging
        # dispatch): inputs already committed to the target device skip
        # device_put entirely — a D2H round-trip saved per elision
        self.staging_transfers = 0
        self.staging_elided = 0
        # bucketed invokes that ran a donating jit (freshly-staged
        # inputs only: the backend owns those buffers, so XLA may reuse
        # their HBM for outputs instead of allocating more)
        self.donated_invokes = 0
        self._donate = False         # resolved in open() (platform gate)
        # compiled multi-step windows (invoke_window): K frames through
        # one lax.scan dispatch — the scheduler-bypass hot path
        self.window_invokes = 0
        self.window_frames = 0
        # window-scan traces are counted apart from compile_count: the
        # latter means "per-frame bucket traces" to bucketing tests and
        # the one-dispatch segment invariants, and a ("win", k) bucket
        # is a second executable over the SAME per-frame bucket, not a
        # new per-frame bucket
        self.window_compile_count = 0
        # observed micro-batch occupancy, {n: invokes} — a first-class
        # sensor (tensor_filter.extra_stats → autotuner bucket
        # refinement) instead of making callers infer occupancy from
        # bucket cache keys
        self.batch_size_hist: Dict[int, int] = {}
        # last successfully bucketed per-frame signature
        # ((frame_shape, dtype), ...) — what stage_bucket() rebuilds a
        # different pow2 bucket from
        self._last_dynb: Optional[tuple] = None
        # cache namespace generation for non-store models: bumped on any
        # model change (reload / shared-entry adoption) and prefixed
        # into every _dyn_jits/_batch_ok key, so a stale bucket compiled
        # against old weights can never be served by key collision
        self._gen = 0
        # per-device cache-namespace suffix: ("dev", id) when the
        # accelerator prop pinned an explicit device ordinal (replica /
        # segment placement, serving/placement.py), else () — folded
        # into _ns() so replicas of one model can never trade compiles
        # across chips by key collision
        self._dev_ns: tuple = ()
        # store:// serving state (serving/store.py): versions are cache-
        # namespaced by version number instead of _gen, adoption happens
        # at invoke boundaries (single worker thread per element ⇒ an
        # invoke sees exactly one version snapshot, never a torn mix)
        self._store_entry = None                 # serving.store._Entry
        self._store_ref = None                   # serving.store.StoreRef
        self._pinned_version: Optional[int] = None
        self._vstates: Dict[int, "_VState"] = {}
        self._adopted_version: Optional[int] = None
        self.adopted_epoch = -1                  # store barrier reads this
        self._canary: Optional[Tuple[int, float]] = None
        self._canary_rng = None
        self._staged: Dict[int, dict] = {}       # version → prewarmed state
        self._served: "OrderedDict[tuple, bool]" = OrderedDict()
        self.swap_count = 0                      # epoch adoptions observed
        # composed device segment (graph/optimize.py fuse_segments):
        # downstream member filters' models trace into THIS backend's
        # jits as (mid_chain_fn | None, member XLABackend, member name)
        # stages — one dispatch runs the whole run. _seg_ps/_seg_sig are
        # the per-invoke member-params snapshot + cache-key signature
        # (refreshed by _seg_begin at every invoke boundary, which is
        # where member store epochs are adopted).
        self._segment: List[tuple] = []
        self._seg_ps: tuple = ()
        self._seg_sig: tuple = ()

    # -- open / model resolution ------------------------------------------
    def open(self, props: Dict[str, Any]) -> None:
        import jax

        model = props.get("model")
        if model is None:
            raise BackendError(
                "framework=xla requires model=<zoo://name | pkg.module:attr "
                "| /path/model.{tflite,npz} | ModelBundle | jax callable>"
            )
        from nnstreamer_tpu.modelio import parse_loader_opts

        opts = parse_loader_opts(props.get("custom") or "")
        self._dynamic_spatial = bool(opts.pop("dynamic_spatial", False))
        # reference-style dedicated props override the custom= string
        for prop, key in (("inputname", "input_names"),
                          ("outputname", "output_names")):
            v = props.get(prop) or ""
            if v:
                opts[key] = [s for s in v.split(",") if s]
        self._loader_opts = opts
        accel = props.get("accelerator") or ""
        self._device = self._pick_device(accel)
        if accel.partition(":")[2]:
            # explicitly-indexed placement (dev i of N): namespace every
            # cache key by the device so no compile travels between chips
            self._dev_ns = ("dev", int(getattr(self._device, "id", 0)))
        # input-buffer donation for bucketed jits ([runtime]
        # donate_inputs): skipped on CPU, where XLA ignores the aliasing
        # hint (host buffers) and would warn per compile
        from nnstreamer_tpu.core.config import get_config

        self._donate = (
            get_config().get_bool("runtime", "donate_inputs", True)
            and getattr(self._device, "platform", "cpu") != "cpu")
        if isinstance(model, str) and model.startswith("store://"):
            self._open_store(model, props)
            return
        key = props.get("shared_tensor_filter_key") or None
        self._shared_key = key
        if key is not None:
            with _shared_lock:
                entry = _shared_models.get(key)
                if entry is None:
                    bundle = self._resolve(model)
                    entry = _SharedEntry(
                        bundle=bundle,
                        device_params=jax.device_put(bundle.params,
                                                     self._device)
                        if bundle.params is not None else None,
                        device=self._device,
                        model_ref=model if isinstance(model, str) else None)
                    _shared_models[key] = entry
                else:
                    # a shared entry is ONE device-resident model: every
                    # holder must agree on what and where it is
                    if entry.device != self._device:
                        raise BackendError(
                            f"shared-tensor-filter-key {key!r} is held on "
                            f"{entry.device} but this filter asked for "
                            f"{self._device}; use a different key per "
                            f"device")
                    if (isinstance(model, str) and entry.model_ref is not None
                            and model != entry.model_ref):
                        raise BackendError(
                            f"shared-tensor-filter-key {key!r} already "
                            f"holds model {entry.model_ref!r}; this filter "
                            f"asked for {model!r} (same key ⇒ same model)")
                entry.holders += 1
                self._shared = entry
                self._bundle = entry.bundle
                self._device_params = entry.device_params
            log.info("opened shared model key=%s holders=%d on %s", key,
                     self._shared.holders, self._device)
            return
        self._bundle = self._resolve(model)
        if self._bundle.params is not None:
            self._device_params = jax.device_put(self._bundle.params, self._device)
        else:
            self._device_params = None
        log.info("opened model %s on %s", self._bundle.name or model, self._device)

    def _open_store(self, model: str, props: Dict[str, Any]) -> None:
        """Bind this backend to a served model in the process-wide
        ModelStore (serving/store.py): resolve the baseline version,
        attach as a swap handle, and set up canary routing when the ref
        carries a split (``store://name@2:0.05``)."""
        import random as _random

        import jax

        if props.get("shared_tensor_filter_key"):
            raise BackendError(
                "store:// models are already process-shared through the "
                "model store; shared-tensor-filter-key cannot combine "
                "with a store reference — drop the key")
        from nnstreamer_tpu.serving.compile_cache import (
            maybe_enable_compile_cache,
        )
        from nnstreamer_tpu.serving.store import get_store, parse_store_ref

        maybe_enable_compile_cache()
        ref = parse_store_ref(model)
        entry = get_store().entry(ref.name)
        self._store_entry = entry
        self._store_ref = ref
        # room for two live versions' bucket sets + a staged prewarm
        self._dyn_cache_max = max(self._dyn_cache_max, 32)
        cur, epoch = entry.state
        if ref.version is not None:
            self._pinned_version = entry.resolve_version(ref.version)
            base = self._pinned_version
        else:
            base = entry.resolve_version(None)
        self._adopted_version = base
        self.adopted_epoch = epoch
        self._vstates[base] = self._make_vstate(base, entry.bundle(base))
        self._bundle = self._vstates[base].bundle
        self._device_params = self._vstates[base].device_params
        if ref.canary_version is not None:
            cv = entry.resolve_version(ref.canary_version)
            if cv == base:
                raise BackendError(
                    f"canary reference {model!r} routes to the baseline "
                    f"version @{base} itself; pick a different version "
                    f"to canary")
            self._canary = (cv, ref.canary_ratio)
            self._canary_rng = _random.Random(
                int(props.get("canary_seed") or 0))
            self._vstates[cv] = self._make_vstate(cv, entry.bundle(cv))
        entry.attach(self)
        log.info("opened store model %s@%d epoch=%d%s on %s", ref.name,
                 base, epoch,
                 f" canary=@{self._canary[0]}:{self._canary[1]}"
                 if self._canary else "", self._device)

    @property
    def tracks_store_epoch(self) -> bool:
        """True when this handle follows ``current`` (un-pinned), i.e.
        participates in the swap barrier."""
        return self._store_entry is not None and self._pinned_version is None

    def _make_vstate(self, version: int, bundle: ModelBundle) -> _VState:
        import jax

        return _VState(
            version=version, bundle=bundle,
            device_params=jax.device_put(bundle.params, self._device)
            if bundle.params is not None else None)

    def _resolve(self, model) -> ModelBundle:
        if isinstance(model, ModelBundle):
            return model
        if isinstance(model, str) and model.startswith("store://"):
            raise BackendError(
                f"{model!r} resolves through the ModelStore at open(); "
                f"store refs cannot nest as version sources — register "
                f"the underlying model instead")
        if callable(model):
            return ModelBundle(
                fn=lambda params, *xs: model(*xs),
                params=None,
                in_spec=getattr(model, "in_spec", None),
                out_spec=getattr(model, "out_spec", None),
                name=getattr(model, "__name__", "callable"),
            )
        if isinstance(model, str) and model.startswith("zoo://"):
            try:
                from nnstreamer_tpu.models.zoo import build_model
            except ImportError as e:
                raise BackendError(f"model zoo unavailable: {e}") from e
            return build_model(model[len("zoo://"):])
        if isinstance(model, str):
            from nnstreamer_tpu import modelio

            ext = model.rsplit(".", 1)[-1].lower() if "." in model else ""
            if ext in modelio.MODEL_EXTENSIONS:
                return modelio.load_model_file(model, **self._loader_opts)
        if isinstance(model, str) and ":" in model:
            mod_name, _, attr = model.partition(":")
            try:
                obj = getattr(importlib.import_module(mod_name), attr)
            except (ImportError, AttributeError) as e:
                raise BackendError(f"cannot load model {model!r}: {e}") from e
            built = obj() if not isinstance(obj, ModelBundle) else obj
            if isinstance(built, ModelBundle):
                return built
            return self._resolve(built)
        raise BackendError(
            f"unrecognized model reference {model!r} for framework=xla; "
            f"expected zoo://<name>, pkg.module:attr, a ModelBundle, or a "
            f"jax callable"
        )

    def _pick_device(self, accelerator: str):
        import jax

        devices = jax.devices()
        if accelerator:
            # "tpu:2" / "tpu" / "cpu" (accl_hw-string analog, hw_accel.c)
            kind, _, idx = accelerator.partition(":")
            matching = [d for d in devices if d.platform.lower() == kind.lower()]
            if not matching:
                raise BackendError(
                    f"accelerator={accelerator!r} but no {kind!r} device is "
                    f"visible; available: "
                    f"{sorted({d.platform for d in devices})}"
                )
            return matching[int(idx)] if idx else matching[0]
        return devices[0]

    def close(self) -> None:
        self._jitted = None
        self._device_params = None
        self._dyn_jits.clear()
        self._batch_ok.clear()
        if self._store_entry is not None:
            # detach the swap handle but keep the entry reference:
            # version_stats() stays readable for post-stop reports
            self._store_entry.detach(self)
            self._vstates.clear()
            self._staged.clear()
        if self._shared is not None:
            with _shared_lock:
                self._shared.holders -= 1
                if self._shared.holders <= 0:
                    _shared_models.pop(self._shared_key, None)
            self._shared = None

    # -- info / negotiation ------------------------------------------------
    def get_model_info(self):
        assert self._bundle is not None, "open() not called"
        return self._bundle.in_spec, self._bundle.out_spec

    def set_input_info(self, in_spec: TensorsSpec) -> TensorsSpec:
        """Shape-infer the model's own output via jax.eval_shape.

        `in_spec` is what the *model* sees (the element already applied
        fused pre-chain spec transfer); fused chains affect invoke()
        only, so eval_shape runs on the bare bundle fn.
        """
        import jax

        assert self._bundle is not None
        self._in_spec = in_spec
        bundle = self._bundle
        bare = lambda params, *xs: _to_tuple(bundle.fn(params, *xs))
        args = [
            jax.ShapeDtypeStruct(t.shape, t.dtype.np_dtype)
            for t in in_spec.tensors
        ]
        try:
            out = jax.eval_shape(bare, self._abstract_params(), *args)
        except Exception as e:
            raise BackendError(
                f"model {self._bundle.name!r} does not accept input "
                f"{in_spec}: {e}"
            ) from e
        self._out_spec = _spec_from_shapes(_to_tuple(out))
        return self._out_spec

    def _abstract_params(self):
        return self._abstract_of(self._device_params)

    @staticmethod
    def _abstract_of(params):
        import jax

        if params is None:
            return None
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params
        )

    # -- fusion ------------------------------------------------------------
    def fuse(self, pre: Optional[ElementwiseFn], post: Optional[ElementwiseFn]) -> bool:
        self._pre = pre
        self._post = post
        # aux constants the post chain needs (e.g. SSD anchors from a
        # fused device decoder). They ride as a jit ARGUMENT, never as a
        # closure constant: a captured array embeds in the program as a
        # literal, recompiled with every bucket and shipped with it
        import jax

        aux = getattr(post, "aux_params", None)
        self._post_aux = None if aux is None else jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._device), aux)
        self._jitted = None  # recompile with the fused graph
        return True

    def compose_segment(self, stages) -> bool:
        """Accept a device segment (graph/optimize.py fuse_segments):
        `stages` is [(mid_chain_fn | None, member_backend, member_name)]
        in dataflow order. Accepting means every member's model traces
        into this backend's jits between the head model and the fused
        post chain — the whole run becomes ONE dispatch, intermediates
        never leave HBM. Declines (→ host-side member invokes in the
        element, bit-identical) when a member can't ride one trace:
        non-XLA backend, different device, a host-side input stage, or
        per-invoke canary routing (the route changes within a buffer
        stream, which a single trace can't express)."""
        for mid, mb, mname in stages:
            if not isinstance(mb, XLABackend):
                log.info("segment declined: member %s is not XLA", mname)
                return False
            if mb._device != self._device:
                log.info("segment declined: member %s is on %s, head on "
                         "%s", mname, mb._device, self._device)
                return False
            if mb._canary is not None:
                log.info("segment declined: member %s has canary "
                         "routing", mname)
                return False
            if any(vs.bundle.host_pre is not None
                   for vs in mb._vstates.values()) or (
                    mb._bundle is not None
                    and mb._bundle.host_pre is not None):
                log.info("segment declined: member %s model has a "
                         "host-side input stage", mname)
                return False
        self._segment = list(stages)
        self._jitted = None
        self._seg_begin()          # initial member params/sig snapshot
        return True

    def _seg_begin(self) -> None:
        """Segment-invoke boundary: adopt flipped member store epochs,
        snapshot member device params (the jit's third packed argument)
        and the cache-key signature. A signature change — any member
        swapped versions — retires the single-path jit; bucketed keys
        carry the signature, so stale compiles simply stop matching."""
        if not self._segment:
            return
        ps: List[Any] = []
        sig: List[tuple] = []
        for _, mb, _ in self._segment:
            if mb._store_entry is not None:
                ver = mb._pick_version()
                ps.append(mb._vstates[ver].device_params)
                sig.append(mb._ns(ver))
            else:
                ps.append(mb._current_params())
                sig.append(mb._ns())
        sig_t = tuple(sig)
        if sig_t != self._seg_sig:
            self._jitted = None
            self._seg_sig = sig_t
        self._seg_ps = tuple(ps)

    def _seg_suffix(self) -> tuple:
        """Cache-key suffix naming every member's version/generation —
        appended (at the END, so _adopt's leading-("v",…) sweeps keep
        working) to every bucketed key and batchability verdict."""
        return (("seg",) + self._seg_sig,) if self._segment else ()

    def _with_seg(self, packed: tuple) -> tuple:
        """Extend a manually-built (params, aux) packed with the member
        params snapshot (prewarm/warm-start paths)."""
        return packed + ((self._seg_ps,) if self._segment else ())

    def _full_fn(self, count: bool = True, bundle: ModelBundle = None):
        bundle = bundle or self._bundle
        pre, post = self._pre, self._post
        seg = list(self._segment)

        def full(packed, *xs):
            params, aux = packed[0], packed[1]
            # member params ride as a jit ARGUMENT (same rule as
            # _post_aux: no weights embedded as program literals);
            # eval_shape callers pass the 2-tuple form and fall back to
            # the concrete member params, which eval_shape tolerates
            segp = packed[2] if len(packed) > 2 else None
            if count:
                # trace-time side effect: counts compilations, not invokes
                self.compile_count += 1
            if pre is not None:
                xs = pre(xs)
            out = _to_tuple(bundle.fn(params, *xs))
            for i, (mid, mb, mname) in enumerate(seg):
                try:
                    if mid is not None:
                        out = _to_tuple(mid(out))
                    mp = segp[i] if segp is not None else mb._device_params
                    out = _to_tuple(mb._bundle.fn(mp, *out))
                except SegmentStageError:
                    raise
                except Exception as e:
                    # trace-time failure inside a member stage: name the
                    # member element, not the surviving head
                    raise SegmentStageError(mname, e) from e
            if post is not None:
                out = post(out) if aux is None else post(out, aux)
            return out

        return full

    def _packed_params(self):
        """(model params, post-chain aux[, member params]) — the jit's
        first argument. Callers must have run _seg_begin this invoke."""
        base = (self._current_params(), getattr(self, "_post_aux", None))
        return base + ((self._seg_ps,) if self._segment else ())

    def _current_params(self):
        """Device params, following shared-entry swaps (hot reload)."""
        if self._shared is not None:
            if self._shared.version != self._jitted_version:
                # a holder reloaded the shared model: recompile against
                # the (possibly different) new bundle fn
                self._bundle = self._shared.bundle
                self._device_params = self._shared.device_params
                self._jitted = None
                self._gen += 1           # new cache namespace
                self._dyn_jits.clear()
                self._batch_ok.clear()
                self._jitted_version = self._shared.version
            return self._shared.device_params
        return self._device_params

    # -- store serving (serving/store.py handle protocol) ------------------
    def _ns(self, version: Optional[int] = None) -> tuple:
        """Cache-namespace prefix: every _dyn_jits/_batch_ok key starts
        with this, so no model change can serve a stale compile by key
        collision — ("v", version) for store models (retired by version
        sweep), ("g", generation) otherwise (cleared + bumped on
        reload/shared adoption). Explicitly-placed backends (replica /
        segment stages) append ("dev", id) so the same model compiled
        for two chips can never collide — _adopt's sweeps read k[0][:2]
        and keep working."""
        if self._store_entry is not None:
            return ("v", version if version is not None
                    else self._adopted_version) + self._dev_ns
        return ("g", self._gen) + self._dev_ns

    def _pick_version(self) -> int:
        """Adopt a flipped epoch, then route this invoke: the pinned
        version (immune to swaps), the canary version at its seeded
        ratio, or the tracked current."""
        e = self._store_entry
        if self._pinned_version is not None:
            return self._pinned_version
        cur, epoch = e.state             # one read = consistent pair
        if epoch != self.adopted_epoch:
            self._adopt(cur, epoch)
        if (self._canary is not None
                and self._canary_rng.random() < self._canary[1]):
            return self._canary[0]
        return self._adopted_version

    def _adopt(self, cur: int, epoch: int) -> None:
        """Flip this backend to the new current version (runs on the
        element's single worker thread, at an invoke boundary): install
        the pre-warmed state staged by `prewarm_version`, retire the
        outgoing version's compiled buckets, and keep self._bundle /
        _device_params pointing at the adopted version so negotiation-
        era paths (eval_shape, flexible invokes) follow along."""
        old = self._adopted_version
        staged = self._staged.pop(cur, None)
        if cur not in self._vstates:
            if staged is not None:
                self._vstates[cur] = staged["vstate"]
            else:                        # un-prewarmed swap: resolve now
                self._vstates[cur] = self._make_vstate(
                    cur, self._store_entry.bundle(cur))
        if staged is not None:
            for basekey, jitted in staged["jits"].items():
                self._insert_jit(
                    (self._ns(cur),) + basekey + self._seg_suffix(), jitted)
        live = {cur}
        if self._canary is not None:
            live.add(self._canary[0])
        if self._pinned_version is not None:
            live.add(self._pinned_version)
        for v in [v for v in self._vstates if v not in live]:
            del self._vstates[v]         # drops old device params
        for cache in (self._dyn_jits, self._batch_ok):
            for k in [k for k in cache
                      if k[0][0] == "v" and k[0][1] not in live]:
                del cache[k]
        self._jitted = None
        vs = self._vstates[cur]
        self._bundle, self._device_params = vs.bundle, vs.device_params
        self._adopted_version, self.adopted_epoch = cur, epoch
        self.swap_count += 1
        self.tracer.record_swap(
            self.trace_name or "xla", time.perf_counter(),
            model=self._store_entry.name, from_version=old,
            to_version=cur, epoch=epoch, prewarmed=staged is not None)
        log.info("adopted %s@%d epoch=%d (from @%s, prewarmed=%s)",
                 self._store_entry.name, cur, epoch, old,
                 staged is not None)

    def prewarm_version(self, version: int, bundle: ModelBundle) -> int:
        """Compile the incoming version against every bucket this
        backend has served, OFF the hot path (called from the
        swap-controller thread, before the epoch flips). The compiled
        jits are staged — the worker installs them at adoption, so the
        post-flip hot path only ever takes cache hits. AOT lower().
        compile() does not populate jit's call cache, so the warmup
        actually CALLS each jit on zero inputs and blocks. A version
        that rejects a served bucket raises here, aborting the swap
        before anything flips. Returns the bucket count compiled."""
        import jax
        import numpy as np_

        from nnstreamer_tpu.runtime.sync import device_sync

        vs = self._make_vstate(version, bundle)
        # NOTE: runs on the swap-controller thread — must NOT call
        # _seg_begin() (worker-owned state); _with_seg reads the last
        # snapshot, which is fine because member params travel as jit
        # ARGUMENTS (the compiled jit serves any same-shaped seg params)
        packed = self._with_seg(
            (vs.device_params, getattr(self, "_post_aux", None)))
        jits: Dict[tuple, Any] = {}
        compiled = 0
        for basekey in list(self._served):
            specs = self._bucket_array_specs(basekey)
            if specs is None:
                continue             # flexible seq/bat: recompile lazily
            if (self._ns(version),) + basekey + self._seg_suffix() \
                    in self._dyn_jits:
                continue             # already live (e.g. was the canary)
            jitted = jax.jit(self._full_fn(bundle=bundle))
            args = tuple(
                jax.device_put(np_.zeros(s, dtype=np_.dtype(d)),
                               self._device) for s, d in specs)
            prof = devprof.get()
            if prof.enabled:
                prof.note_dispatch(self._prof_label(),
                                   devprof.bucket_label(basekey))
            t0 = time.perf_counter()
            try:
                out = _to_tuple(jitted(packed, *args))
                device_sync(out, self.tracer, self.trace_name)
            except Exception as e:
                raise BackendError(
                    f"pre-warm of {self._store_entry.name}@{version} "
                    f"failed on served bucket {basekey[0]} "
                    f"{[s for s, _ in specs]}: {e} — swap aborted before "
                    f"the epoch flip; the serving version is unchanged"
                ) from e
            self._prof_capture(devprof.bucket_label(basekey), jitted,
                               (packed,) + args,
                               time.perf_counter() - t0)
            jits[basekey] = jitted
            compiled += 1
        self._staged[version] = {"vstate": vs, "jits": jits}
        return compiled

    def warm_start(self) -> int:
        """Replay the persistent manifest's bucket set for the bound
        version (called by tensor_filter.start(), off the hot path):
        against a warm XLA disk cache these compile as fast loads, so a
        restarted process serves its first real buffer from cache."""
        if self._store_entry is None:
            return 0
        import jax
        import numpy as np_

        from nnstreamer_tpu.runtime.sync import device_sync
        from nnstreamer_tpu.serving.compile_cache import manifest_buckets

        self._seg_begin()        # single-threaded (tensor_filter.start)
        ver = self._adopted_version
        vs = self._vstates.get(ver)
        if vs is None:
            return 0
        packed = self._with_seg(
            (vs.device_params, getattr(self, "_post_aux", None)))
        compiled = 0
        for basekey in manifest_buckets(self._store_entry.name, ver):
            key = (self._ns(ver),) + basekey + self._seg_suffix()
            if key in self._dyn_jits:
                continue
            specs = self._bucket_array_specs(basekey)
            if specs is None:
                continue
            prof = devprof.get()
            t0 = time.perf_counter()
            try:
                jitted = jax.jit(self._full_fn(bundle=vs.bundle))
                args = tuple(
                    jax.device_put(np_.zeros(s, dtype=np_.dtype(d)),
                                   self._device) for s, d in specs)
                if prof.enabled:
                    prof.note_dispatch(self._prof_label(),
                                       devprof.bucket_label(basekey))
                device_sync(_to_tuple(jitted(packed, *args)),
                            self.tracer, self.trace_name)
            except Exception as e:
                # stale manifest (model changed shape since it was
                # written): warm start is an optimization, never a gate
                log.warning("warm-start bucket %s skipped: %s",
                            basekey[:2], e)
                continue
            self._prof_capture(devprof.bucket_label(basekey), jitted,
                               (packed,) + args,
                               time.perf_counter() - t0)
            self._insert_jit(key, jitted)
            self._served.setdefault(basekey, True)
            compiled += 1
        if compiled:
            log.info("warm start: %d manifest buckets compiled for %s@%d",
                     compiled, self._store_entry.name, ver)
        return compiled

    @staticmethod
    def _bucket_array_specs(basekey: tuple):
        """(shape, dtype) list to materialize a recorded bucket, or None
        for kinds that are not replayed (flexible seq/bat)."""
        kind = basekey[0]
        if kind == "fix":
            return list(basekey[1:])
        if kind == "dynb":
            return list(basekey[2:])
        return None

    def _note_bucket(self, version: int, basekey: tuple) -> None:
        if basekey not in self._served:
            self._served[basekey] = True
            self._store_entry.note_bucket(version, basekey)

    def version_stats(self) -> Dict[int, dict]:
        """Per-version invoke/error/p95 counters of the bound store
        entry (process-wide across handles), for extra_stats."""
        if self._store_entry is None:
            return {}
        return self._store_entry.stats_dict()

    def _record_invoke(self, version: int, t0: float,
                       error: bool = False) -> float:
        dt = time.perf_counter() - t0
        self._store_entry.record(version, dt, error=error)
        return dt

    # -- device performance plane (runtime/devprof.py) ---------------------
    def _prof_label(self) -> str:
        """Stable filter label for devprof keys: the element's trace
        name, else the store model name, else the bundle name."""
        if self.trace_name:
            return self.trace_name
        if self._store_entry is not None:
            return self._store_entry.name
        if self._bundle is not None and self._bundle.name:
            return self._bundle.name
        return "xla"

    def _prof_capture(self, bucket: str, jitted, args: tuple,
                      seconds: float) -> None:
        """Compile-event hook: register this backend for HBM
        attribution and hand the jitted program + concrete args to the
        profiler's cost-model capture (a re-lower, compile misses
        only — never the steady-state hot path)."""
        prof = devprof.get()
        if not prof.enabled:
            return
        label = self._prof_label()
        prof.attach_model(label, self)
        prof.capture_cost(label, bucket, jitted, args, seconds=seconds)

    def _stage(self, arrs) -> Tuple[ArrayTuple, bool]:
        """Move inputs to the target device, skipping `device_put` for
        arrays **already committed there** (a committed jax.Array whose
        device set is exactly {target} is resident by definition — e.g.
        a device-side decoder's output feeding a second filter). Returns
        (staged, all_fresh): all_fresh is True only when every buffer
        was host-side, i.e. every device buffer in `staged` was created
        right here and is exclusively ours — the precondition for
        handing them to a donating jit. Elided arrays are upstream-owned
        and must never be donated."""
        import jax

        dev = self._device
        staged = []
        fresh = True
        for a in arrs:
            if getattr(a, "committed", False) and a.devices() == {dev}:
                self.staging_elided += 1
                staged.append(a)
                fresh = False
            else:
                self.staging_transfers += 1
                staged.append(jax.device_put(a, dev))
        return tuple(staged), fresh

    def _invoke_store(self, tensors: ArrayTuple) -> ArrayTuple:
        """Fixed-shape invoke through the store routing point: pick the
        version (adopting a flipped epoch first), then run its bucketed
        jit. Keys carry shape+dtype so the bucket is pre-warmable and
        manifest-replayable."""
        import jax
        import numpy as np_

        ver = self._pick_version()
        vs = self._vstates[ver]
        if vs.bundle.host_pre is not None:
            tensors = tuple(vs.bundle.host_pre(tuple(tensors)))
        arrs = tuple(t if hasattr(t, "shape") else np_.asarray(t)
                     for t in tensors)
        basekey = ("fix",) + tuple(
            (tuple(a.shape), str(a.dtype)) for a in arrs)
        self._note_bucket(ver, basekey)
        packed = self._with_seg(
            (vs.device_params, getattr(self, "_post_aux", None)))
        hits0 = self.cache_hits
        jitted = self._bucket_jit(
            (self._ns(ver),) + basekey + self._seg_suffix(),
            make=lambda: jax.jit(self._full_fn(bundle=vs.bundle)))
        staged, _ = self._stage(arrs)
        prof = devprof.get()
        blabel = devprof.bucket_label(basekey)
        if prof.enabled:
            prof.note_dispatch(self._prof_label(), blabel)
        t0 = time.perf_counter()
        try:
            out = _to_tuple(jitted(packed, *staged))
        except Exception:
            self._record_invoke(ver, t0, error=True)
            raise
        dt = self._record_invoke(ver, t0)
        if prof.enabled and self.cache_hits == hits0:
            self._prof_capture(blabel, jitted, (packed,) + staged, dt)
        tr = self.tracer
        if tr.active:
            tr.backend_span(self.trace_name or "xla", "invoke", t0,
                            t0 + dt, version=ver,
                            compile="cached" if self.cache_hits > hits0
                            else "fresh")
        return out

    # -- hot loop ----------------------------------------------------------
    def invoke(self, tensors: ArrayTuple) -> ArrayTuple:
        import jax

        self._seg_begin()
        if self._store_entry is not None:
            return self._invoke_store(tensors)
        if self._bundle.host_pre is not None:
            tensors = tuple(self._bundle.host_pre(tuple(tensors)))
        params = self._packed_params()
        fresh = self._jitted is None
        if fresh:
            self._jitted = jax.jit(self._full_fn())
        # explicit async H2D staging before dispatch: the transfer
        # overlaps the previous frame's compute; already-device-committed
        # inputs skip the put entirely
        staged, _ = self._stage(tensors)
        tr = self.tracer
        prof = devprof.get()
        if prof.enabled:
            prof.note_dispatch(self._prof_label(), "static")
        if tr.active or (prof.enabled and fresh):
            t0 = time.perf_counter()
            out = self._jitted(params, *staged)
            t1 = time.perf_counter()
            if tr.active:
                tr.backend_span(self.trace_name or "xla", "invoke", t0,
                                t1, compile="fresh" if fresh else "cached")
            if prof.enabled and fresh:
                self._prof_capture("static", self._jitted,
                                   (params,) + staged, t1 - t0)
        else:
            out = self._jitted(params, *staged)
        return _to_tuple(out)

    def invoke_window(self, frames: List[ArrayTuple]) -> List[ArrayTuple]:
        """Compiled multi-step window: K same-signature frames through
        ONE ``jax.lax.scan`` whose body is exactly the per-frame full
        function — one Python dispatch, one device program, K frames.
        This is the scheduler-bypass hot path: the steady-state loop
        (runtime/compiled_loop.py) collects the window, this runs it.

        Guarantees the scheduler's bail matrix leans on:

        - the scan body IS `_full_fn`, so outputs are bit-identical to
          K per-frame invokes of the same bucket;
        - version pick / epoch adoption happens ONCE at the window
          boundary (the scheduler bails to per-frame when it sees a
          pending swap, so adoption never lands mid-window);
        - store invoke accounting records K invokes of dt/K each —
          per-version counters reconcile exactly with per-frame mode.
        """
        import jax
        import numpy as np_

        k = len(frames)
        self._seg_begin()
        if self._store_entry is not None:
            ver = self._pick_version()
            vs = self._vstates[ver]
            bundle = vs.bundle
            ns = self._ns(ver)
            packed = self._with_seg(
                (vs.device_params, getattr(self, "_post_aux", None)))
        else:
            ver = None
            bundle = self._bundle
            ns = self._ns()
            self._current_params()     # follow shared-entry reloads
            packed = self._packed_params()
        if bundle.host_pre is not None:
            frames = [tuple(bundle.host_pre(tuple(f))) for f in frames]
        n_in = len(frames[0])
        stacked = tuple(
            np_.stack([np_.asarray(f[i]) for f in frames], axis=0)
            for i in range(n_in))
        basekey = ("win", k) + tuple(
            (tuple(a.shape[1:]), str(a.dtype)) for a in stacked)
        full = self._full_fn(count=False,
                             bundle=bundle if ver is not None else None)
        staged, _ = self._stage(stacked)

        def make():
            def window_fn(p, *xs):
                def body(carry, x):
                    return carry, _to_tuple(full(carry, *x))
                _, ys = jax.lax.scan(body, p, xs)
                return ys
            # built here, ahead of the guarded execution: a scan that
            # cannot be traced or compiled for the device is the
            # window's failure, not a frame's, and must surface
            try:
                built = jax.jit(window_fn).lower(packed, *staged).compile()
            except Exception as e:
                raise WindowBuildError(
                    f"{self.trace_name or 'xla'}: the {k}-frame compiled "
                    f"window could not be built for {self._device} "
                    f"({type(e).__name__}: {e}); set compiled_loop=false "
                    f"on the filter to serve per-frame") from e
            self.window_compile_count += 1
            return built

        jitted = self._bucket_jit((ns,) + basekey + self._seg_suffix(),
                                  make=make)
        prof = devprof.get()
        if prof.enabled:
            prof.note_dispatch(self._prof_label(), f"win:{k}")
        t0 = time.perf_counter()
        try:
            ys = _to_tuple(jitted(packed, *staged))
        except Exception:
            if ver is not None:
                self._record_invoke(ver, t0, error=True)
            raise
        dt = time.perf_counter() - t0
        if ver is not None:
            # K invokes of dt/K each: the per-version ledger counts the
            # same frames whether or not the window path served them
            for _ in range(k):
                self._store_entry.record(ver, dt / k)
        self.window_invokes += 1
        self.window_frames += k
        tr = self.tracer
        if tr.active:
            tr.backend_span(self.trace_name or "xla", "invoke_window",
                            t0, t0 + dt, frames=k,
                            **({"version": ver} if ver is not None
                               else {}))
        # unstack: row i of every output is frame i's output tuple
        return [tuple(y[i] for y in ys) for i in range(k)]

    def swap_pending(self) -> bool:
        """True when the bound store entry flipped epochs since this
        backend last adopted — the scheduler's compiled loop checks
        this at window entry and bails to per-frame mode so adoption
        happens at an ordinary invoke boundary (bail cause "swap")."""
        if self._store_entry is None or self._pinned_version is not None:
            return False
        _, epoch = self._store_entry.state
        return epoch != self.adopted_epoch

    # -- flexible shapes (invoke-dynamic analog) ---------------------------
    def invoke_flexible(self, regions: List[Any]) -> List[Any]:
        """Run the model over per-buffer variable-shape regions (e.g.
        tensor_crop output) with a **bounded, bucketed** compile policy
        (SURVEY §7 hard part d; reference invoke-dynamic,
        tensor_filter_common.c:899-1017):

        - same-shape regions are stacked along the batch axis, padded to
          the next power-of-two batch bucket, and run as ONE batched XLA
          call (MXU-friendly) — tried via eval_shape first, with a
          per-region fallback for models with a baked-in batch (tflite);
        - with custom=dynamic_spatial=true, spatial dims are additionally
          zero-padded up to power-of-two buckets (≥16) so arbitrary crop
          sizes reuse a small set of compilations — valid for
          shape-polymorphic models (global-pool classifiers);
        - compiled variants live in an LRU of {_dyn_cache_max} entries.
        """
        import jax
        import numpy as np_

        if self._store_entry is not None and self._pinned_version is None:
            # adopt a flipped epoch at the buffer boundary; flexible
            # invokes always run the adopted current (no canary split —
            # per-region shapes make the ratio bookkeeping meaningless)
            cur, epoch = self._store_entry.state
            if epoch != self.adopted_epoch:
                self._adopt(cur, epoch)
        if self._bundle.host_pre is not None:
            raise BackendError(
                f"model {self._bundle.name!r} has a host-side input "
                f"stage (host_pre) which the flexible-shape path does "
                f"not support; use the fixed-shape invoke path")
        params = self._packed_params()
        rs = [np_.asarray(r) if not hasattr(r, "shape") else r
              for r in regions]
        out: List[Any] = [None] * len(rs)
        groups: Dict[tuple, List[int]] = {}
        for i, r in enumerate(rs):
            groups.setdefault(tuple(r.shape), []).append(i)

        for shape, idxs in groups.items():
            arrs = [rs[i] for i in idxs]
            if self._dynamic_spatial and len(shape) >= 3:
                # pad (…, H, W, C) spatial dims up to pow2 buckets ≥16
                pads = []
                padded_shape = list(shape)
                for ax in (len(shape) - 3, len(shape) - 2):
                    b = next_pow2(shape[ax], 16)
                    pads.append((ax, b - shape[ax]))
                    padded_shape[ax] = b
                if any(p for _, p in pads):
                    widths = [(0, 0)] * len(shape)
                    for ax, p in pads:
                        widths[ax] = (0, p)
                    arrs = [np_.pad(np_.asarray(a), widths) for a in arrs]
                    shape = tuple(padded_shape)
            n = len(arrs)
            batched, nb, stacked = self._batch_group(arrs, shape, n)
            if batched is None:       # model can't batch: sequential path
                jitted = self._bucket_jit((self._ns(), "seq") + shape)
                for i, a in zip(idxs, arrs):
                    out[i] = _to_tuple(jitted(params, a))[0]
                continue
            jitted = self._bucket_jit((self._ns(), "bat", nb) + shape)
            res = _to_tuple(jitted(params, batched))[0]
            for k, i in enumerate(idxs):
                out[i] = res[k:k + 1] if not stacked else res[k]
        return out

    def _batch_group(self, arrs, shape, n):
        """Stack same-shape regions into one batch-bucketed array, or
        (None, 0, False) if the model rejects a batched input shape. The
        batchability verdict is cached per (batched shape, dtype) so the
        hot loop never re-traces eval_shape for a recurring crop shape."""
        import jax
        import numpy as np_

        nb = next_pow2(n)
        if shape[0] == 1:
            batched_shape = (nb,) + shape[1:]
            stacked = False
        else:
            batched_shape = (nb,) + shape
            stacked = True
        dt = np_.asarray(arrs[0]).dtype
        verdict_key = (self._ns(), batched_shape, str(dt))
        ok = self._batch_ok.get(verdict_key)
        if ok is None:
            try:
                args = [jax.ShapeDtypeStruct(batched_shape, dt)]
                jax.eval_shape(lambda p, x: self._full_fn(count=False)(p, x),
                               (self._abstract_params(),
                                getattr(self, "_post_aux", None)), *args)
                ok = True
            except Exception:
                ok = False
            self._batch_ok[verdict_key] = ok
        if not ok:
            return None, 0, False
        big = np_.concatenate if not stacked else np_.stack
        block = big([np_.asarray(a) for a in arrs], axis=0)
        if nb > block.shape[0]:
            fill = np_.repeat(block[-1:], nb - block.shape[0], axis=0)
            block = np_.concatenate([block, fill], axis=0)
        return block, nb, stacked

    # -- dynamic micro-batches (tensor_batch upstream) ---------------------
    def invoke_batched(self, tensors, n: int, keepdims=()):
        """One batched XLA call per micro-batch, padded to the next
        power-of-two occupancy bucket so ragged batch sizes (deadline
        flushes under varying load) reuse at most log2(max_batch)
        compilations instead of one per occupancy. Shares the LRU'd
        `_dyn_jits` cache and `compile_count` with invoke_flexible.

        Falls back to the per-frame base path when the model rejects a
        batched input shape (baked-in batch dim) or needs host_pre."""
        import jax
        import numpy as np_

        self._seg_begin()
        if self._store_entry is not None:
            return self._invoke_batched_store(tensors, n, keepdims)
        if self._bundle.host_pre is not None:
            # host_pre parses per-frame bytes; it has no batched form
            return super().invoke_batched(tensors, n, keepdims)
        nb = next_pow2(n)
        # keep device-resident micro-batches as-is (asarray would force
        # a D2H readback just to re-upload them a few lines down)
        arrs = [t if hasattr(t, "shape") else np_.asarray(t)
                for t in tensors]
        batched_shapes = tuple((nb,) + tuple(a.shape[1:]) for a in arrs)
        verdict_key = (self._ns(), "dynb") + tuple(
            (s, str(a.dtype)) for s, a in zip(batched_shapes, arrs)) \
            + self._seg_suffix()
        ok = self._batch_ok.get(verdict_key)
        if ok is None:
            try:
                args = [jax.ShapeDtypeStruct(s, a.dtype)
                        for s, a in zip(batched_shapes, arrs)]
                jax.eval_shape(self._full_fn(count=False),
                               (self._abstract_params(),
                                getattr(self, "_post_aux", None)), *args)
                ok = True
            except Exception:
                ok = False
            self._batch_ok[verdict_key] = ok
        if not ok:
            return super().invoke_batched(tensors, n, keepdims)
        self.batch_size_hist[n] = self.batch_size_hist.get(n, 0) + 1
        self._last_dynb = tuple(
            (tuple(a.shape[1:]), str(a.dtype)) for a in arrs)
        arrs = self._pad_bucket(arrs, n, nb)
        params = self._packed_params()
        hits0 = self.cache_hits
        staged, fresh = self._stage(arrs)
        # donation: only when every device buffer was staged right here
        # (we own them all); the donating variant is its own cache entry
        donate = self._donate and fresh
        key = (self._ns(), "dynb", nb) + batched_shapes \
            + self._seg_suffix()
        if donate:
            self.donated_invokes += 1
            dn = tuple(range(1, 1 + len(staged)))
            jitted = self._bucket_jit(
                key + ("don",),
                make=lambda: jax.jit(self._full_fn(), donate_argnums=dn))
        else:
            jitted = self._bucket_jit(key)
        tr = self.tracer
        prof = devprof.get()
        miss = self.cache_hits == hits0
        blabel = f"dynb:{nb}"
        if prof.enabled:
            prof.note_dispatch(self._prof_label(), blabel)
        if tr.active or (prof.enabled and miss):
            t0 = time.perf_counter()
            out = _to_tuple(jitted(params, *staged))
            t1 = time.perf_counter()
            if tr.active:
                tr.backend_span(self.trace_name or "xla",
                                "invoke_batched", t0, t1, n=n, bucket=nb,
                                cache="miss" if miss else "hit")
            if prof.enabled and miss:
                self._prof_capture(blabel, jitted,
                                   (params,) + tuple(staged), t1 - t0)
        else:
            out = _to_tuple(jitted(params, *staged))
        return tuple(o[:n] for o in out)

    @staticmethod
    def _pad_bucket(arrs, n: int, nb: int):
        """Pad a micro-batch up to its pow2 bucket by repeating the last
        frame's rows: real data keeps padded lanes numerically tame (vs
        zeros hitting e.g. a divide), and the pad rows are sliced away
        before anyone sees them. Device-resident inputs pad on device
        (numpy concatenate would pull them back to host)."""
        import numpy as np_

        if nb <= n:
            return arrs
        out = []
        for a in arrs:
            if type(a).__module__.startswith("jax"):
                import jax.numpy as xp
            else:
                xp = np_
            out.append(xp.concatenate(
                [a, xp.repeat(a[-1:], nb - n, axis=0)], axis=0))
        return out

    def _invoke_batched_store(self, tensors, n: int, keepdims=()):
        """Micro-batched invoke through the store routing point: the
        whole micro-batch goes to ONE version (canary granularity is
        the buffer). Bucket keys are version-namespaced and carry
        shape+dtype so `prewarm_version` can compile the exact set the
        outgoing version served."""
        import jax
        import numpy as np_

        ver = self._pick_version()
        vs = self._vstates[ver]
        if vs.bundle.host_pre is not None:
            return super().invoke_batched(tensors, n, keepdims)
        nb = next_pow2(n)
        arrs = [t if hasattr(t, "shape") else np_.asarray(t)
                for t in tensors]
        pairs = tuple(((nb,) + tuple(a.shape[1:]), str(a.dtype))
                      for a in arrs)
        basekey = ("dynb", nb) + pairs
        verdict_key = (self._ns(ver),) + basekey + self._seg_suffix()
        ok = self._batch_ok.get(verdict_key)
        if ok is None:
            try:
                args = [jax.ShapeDtypeStruct(s, np_.dtype(d))
                        for s, d in pairs]
                jax.eval_shape(self._full_fn(count=False,
                                             bundle=vs.bundle),
                               (self._abstract_of(vs.device_params),
                                getattr(self, "_post_aux", None)), *args)
                ok = True
            except Exception:
                ok = False
            self._batch_ok[verdict_key] = ok
        if not ok:
            return super().invoke_batched(tensors, n, keepdims)
        self.batch_size_hist[n] = self.batch_size_hist.get(n, 0) + 1
        self._last_dynb = tuple(
            (tuple(a.shape[1:]), str(a.dtype)) for a in arrs)
        arrs = self._pad_bucket(arrs, n, nb)
        self._note_bucket(ver, basekey)
        packed = self._with_seg(
            (vs.device_params, getattr(self, "_post_aux", None)))
        hits0 = self.cache_hits
        staged, fresh = self._stage(arrs)
        donate = self._donate and fresh
        if donate:
            self.donated_invokes += 1
            dn = tuple(range(1, 1 + len(staged)))
            jitted = self._bucket_jit(
                verdict_key + ("don",),
                make=lambda: jax.jit(self._full_fn(bundle=vs.bundle),
                                     donate_argnums=dn))
        else:
            jitted = self._bucket_jit(
                verdict_key,
                make=lambda: jax.jit(self._full_fn(bundle=vs.bundle)))
        prof = devprof.get()
        blabel = devprof.bucket_label(basekey)
        if prof.enabled:
            prof.note_dispatch(self._prof_label(), blabel)
        t0 = time.perf_counter()
        try:
            out = _to_tuple(jitted(packed, *staged))
        except Exception:
            self._record_invoke(ver, t0, error=True)
            raise
        dt = self._record_invoke(ver, t0)
        if prof.enabled and self.cache_hits == hits0:
            self._prof_capture(blabel, jitted,
                               (packed,) + tuple(staged), dt)
        tr = self.tracer
        if tr.active:
            tr.backend_span(self.trace_name or "xla", "invoke_batched",
                            t0, t0 + dt, n=n, bucket=nb, version=ver,
                            cache="hit" if self.cache_hits > hits0
                            else "miss")
        return tuple(o[:n] for o in out)

    def _bucket_jit(self, key: tuple, make=None):
        import jax

        jitted = self._dyn_jits.pop(key, None)
        if jitted is None:
            self.cache_misses += 1
            jitted = jax.jit(self._full_fn()) if make is None else make()
            if len(self._dyn_jits) >= self._dyn_cache_max:
                evicted, _ = self._dyn_jits.popitem(last=False)
                log.info("dyn-shape cache full: evicted %s", evicted)
        else:
            self.cache_hits += 1
        self._dyn_jits[key] = jitted      # re-insert = LRU touch
        return jitted

    def _insert_jit(self, key: tuple, jitted) -> None:
        """Install a pre-compiled jit (staged prewarm / manifest replay)
        without touching the hit/miss counters — these compiles happened
        off the hot path."""
        if key in self._dyn_jits:
            return
        if len(self._dyn_jits) >= self._dyn_cache_max:
            self._dyn_jits.popitem(last=False)
        self._dyn_jits[key] = jitted

    def stage_bucket(self, nb: int) -> bool:
        """Compile the pow2 occupancy bucket ``nb`` for the most
        recently served dynamic-batch signature, OFF the hot path, and
        install it via `_insert_jit` — the autotuner stages a refined
        bucket here *before* flipping ``tensor_batch``'s ``max_batch``,
        so the first flush at the new size takes a cache hit instead of
        an in-band recompile stall. Safe to call from the controller
        thread: it never touches worker-owned seg state (`_seg_begin`),
        and a concurrent `_insert_jit` against the worker's LRU is at
        worst one transient extra cache entry. Returns True when the
        bucket is live (freshly compiled or already cached)."""
        pairs = self._last_dynb
        if pairs is None or nb < 1:
            return False
        import jax
        import numpy as np_

        from nnstreamer_tpu.runtime.sync import device_sync

        nb = next_pow2(int(nb))
        batched = tuple(((nb,) + tuple(s), d) for s, d in pairs)
        ver = None
        if self._store_entry is not None:
            ver = self._adopted_version
            vs = self._vstates.get(ver)
            if vs is None:
                return False
            basekey = ("dynb", nb) + batched
            key = (self._ns(ver),) + basekey + self._seg_suffix()
            fn = self._full_fn(bundle=vs.bundle)
            packed = self._with_seg(
                (vs.device_params, getattr(self, "_post_aux", None)))
        else:
            key = (self._ns(), "dynb", nb) \
                + tuple(s for s, _ in batched) + self._seg_suffix()
            fn = self._full_fn()
            packed = self._packed_params()
        if key in self._dyn_jits:
            return True
        prof = devprof.get()
        t0 = time.perf_counter()
        try:
            jitted = jax.jit(fn)
            args = tuple(
                jax.device_put(np_.zeros(s, dtype=np_.dtype(d)),
                               self._device) for s, d in batched)
            if prof.enabled:
                prof.note_dispatch(self._prof_label(), f"dynb:{nb}")
            device_sync(_to_tuple(jitted(packed, *args)),
                        self.tracer, self.trace_name)
        except Exception as e:
            log.warning("stage_bucket(%d) skipped: %s", nb, e)
            return False
        self._prof_capture(f"dynb:{nb}", jitted, (packed,) + args,
                           time.perf_counter() - t0)
        self._insert_jit(key, jitted)
        if ver is not None:
            self._note_bucket(ver, basekey)
        return True

    # -- residency pressure hooks (serving/tenancy.ModelResidency) ---------
    def jit_cache_size(self) -> int:
        """Live compiled entries (bucketed jits + the static-path jit).
        A model with zero is 'cold': releasing it again is free."""
        return len(self._dyn_jits) + (1 if self._jitted is not None else 0)

    def release_compiled(self) -> int:
        """Drop every compiled artifact (LRU eviction under memory
        pressure — serving/tenancy.ModelResidency). Params, specs, and
        store attachments stay: the next invoke recompiles the needed
        bucket (a counted cache miss), results are bitwise unchanged.
        Returns the number of entries released."""
        n = self.jit_cache_size()
        self._dyn_jits.clear()
        self._batch_ok.clear()
        self._jitted = None
        return n

    @staticmethod
    def _tree_bytes(params) -> int:
        import jax

        if params is None:
            return 0
        return sum(
            getattr(a, "nbytes", 0)
            for a in jax.tree_util.tree_leaves(params))

    def resident_bytes(self) -> int:
        """Device bytes held by this model's params (all resident store
        versions, or the single non-store param tree)."""
        if self._vstates:
            return sum(self._tree_bytes(vs.device_params)
                       for vs in self._vstates.values())
        return self._tree_bytes(self._device_params)

    def resident_bytes_by_version(self) -> Dict[str, int]:
        """Per-resident-version device bytes ({"v<N>": bytes}) — the
        devprof HBM ledger's per-model-version attribution; empty for
        non-store models (the plain resident_bytes row covers those)."""
        return {f"v{ver}": self._tree_bytes(vs.device_params)
                for ver, vs in sorted(self._vstates.items())}

    def reload(self, model: Any) -> None:
        """Hot model swap (is-updatable analog): double-buffered — the new
        bundle is resolved and staged before the old one is dropped. For a
        shared model, the swap updates the shared entry so ALL holders
        pick it up on their next invoke."""
        import jax

        if self._store_entry is not None:
            raise BackendError(
                f"this filter serves {self._store_entry.name!r} through "
                f"the model store; per-filter reload would fork it from "
                f"the registry — register the new weights as a version "
                f"and ModelStore.update({self._store_entry.name!r}, "
                f"<version>) instead (or `python -m nnstreamer_tpu "
                f"models swap`)")
        new_bundle = self._resolve(model)
        new_params = (
            jax.device_put(new_bundle.params, self._device)
            if new_bundle.params is not None
            else None
        )
        if self._shared is not None:
            with _shared_lock:
                self._shared.bundle = new_bundle
                self._shared.device_params = new_params
                self._shared.version += 1
            return
        self._bundle, self._device_params = new_bundle, new_params
        self._jitted = None
        self._gen += 1               # new cache namespace
        self._dyn_jits.clear()
        self._batch_ok.clear()
