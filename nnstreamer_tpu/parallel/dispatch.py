"""Pod batch dispatcher — mesh-sharded streaming inference.

North-star replacement for the reference's per-frame TCP request/reply
offload (`tensor_query_client` → server, SURVEY.md §3.4): instead of one
frame per round-trip, frames from any number of streams are coalesced
into batches, sharded over the mesh's dp axis, and executed as one pjit
computation whose collectives ride ICI. Off-pod clients still reach this
through edge/ (parity transport); on-pod, elements call it directly.

Flow: submit(frame) → future; a collector thread packs up to
`max_batch` frames (or flushes after `max_delay_ms`), pads the batch to
the bucket size (static shapes — no recompiles), runs the sharded fn,
and resolves futures with per-frame outputs.

The collector/completion machinery lives in `BatchCore`, shared with
the serving placement layer (serving/placement.py): each data-parallel
replica there is one BatchCore bound to one device, so per-chip queues
get the same linger/pad/overlap-D2H/count-before-resolve discipline the
mesh path has.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nnstreamer_tpu.core.errors import StreamError
from nnstreamer_tpu.core.log import get_logger

log = get_logger("parallel.dispatch")


class BatchCore:
    """Collector + completion stages behind a submit() → Future API.

    `run(batch, n)` is the device computation: `batch` is a numpy array
    already padded to one of the compiled `buckets` sizes, `n` the
    number of real frames at its front; it returns one device array or
    a tuple of them, resolved per-frame as host tuples.

    `capacity` bounds the replica queue: submit() raises a typed
    StreamError once `outstanding` (accepted but unresolved frames)
    reaches it, so a slow chip backpressures its callers instead of
    buffering unboundedly (0 = unbounded, the mesh dispatcher's
    historical behaviour).

    `raw=True` switches the payload currency from stackable arrays to
    opaque invocation payloads: no squeeze/stack/pad, `run(items, n)`
    gets the payload list verbatim and returns one output tuple per
    item. The serving replica path uses this — its unit of routing is
    a whole filter invocation (a tensor tuple or a micro-batch), not a
    single frame.

    Conservation contract (same as the worker pool's): counters are
    bumped under `_lock` BEFORE futures resolve, so a caller that
    observed its result and then read stats() always sees its own
    frame counted; every accepted frame ends in exactly one of
    frames / errors / shutdown-failed.
    """

    def __init__(self, run: Callable[[Any, int], Any],
                 buckets: Sequence[int], max_delay_s: float, *,
                 capacity: int = 0, raw: bool = False,
                 name: str = "dispatch"):
        self._run = run
        self.buckets = sorted({int(b) for b in buckets})
        if not self.buckets or self.buckets[0] < 1:
            raise StreamError(f"bad bucket set {buckets!r}")
        self.max_delay = max_delay_s
        self.capacity = int(capacity)
        self.raw = bool(raw)
        self.name = name
        self._pending: List[Tuple[Any, Future]] = []
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._shutdown_done = False
        # perf counters — mutated under _lock; read via stats() for a
        # consistent snapshot (bare attribute reads see a live value)
        self.frames = 0
        self.batches = 0
        self.errors = 0
        self._outstanding = 0
        self._thread = threading.Thread(target=self._loop, name=name,
                                        daemon=True)
        self._thread.start()
        # completion stage: device results queue here and a second
        # thread performs the host readback + future resolution, so the
        # batcher can dispatch batch N+1 while batch N's D2H is still in
        # flight
        import queue as _q

        self._done_q: "_q.Queue" = _q.Queue(maxsize=4)
        self._completer = threading.Thread(target=self._complete_loop,
                                           name=f"{name}-complete",
                                           daemon=True)
        self._completer.start()

    # -- client API --------------------------------------------------------
    def submit(self, frame) -> Future:
        """frame: single-sample array (no batch dim or batch=1)."""
        fut: Future = Future()
        with self._lock:
            if self._stop:
                raise StreamError(f"{self.name}: dispatcher is shut down")
            if self.capacity and self._outstanding >= self.capacity:
                raise StreamError(
                    f"{self.name}: queue full "
                    f"({self._outstanding}/{self.capacity} outstanding)")
            self._outstanding += 1
            self._pending.append((frame, fut))
        self._wake.set()
        return fut

    def infer(self, frame, timeout: Optional[float] = 30.0):
        return self.submit(frame).result(timeout)

    @property
    def outstanding(self) -> int:
        """Frames accepted but not yet resolved (queue depth + in
        flight on device) — the least-outstanding router's load signal."""
        with self._lock:
            return self._outstanding

    def stats(self) -> dict:
        """Consistent counter snapshot (one lock hold — the counters
        are incremented together under _lock, so frames/batches never
        tear mid-batch)."""
        with self._lock:
            return {"frames": self.frames, "batches": self.batches,
                    "errors": self.errors,
                    "outstanding": self._outstanding}

    def shutdown(self, cause: str = "shut down") -> None:
        # idempotent: a second shutdown (supervisor drain racing a user
        # close) must not double-join or enqueue a second sentinel
        with self._lock:
            if self._shutdown_done:
                return
            self._shutdown_done = True
            self._stop = True
        self._wake.set()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            log.warning("dispatcher: batcher thread %s still alive after "
                        "30s join at shutdown — thread leaked",
                        self._thread.name)
        # the batcher normally drains _pending before exiting; if it
        # died or wedged, fail the leftovers with a typed error instead
        # of leaving callers blocked on futures nobody will resolve
        with self._lock:
            leftover = self._pending
            self._pending = []
            self._outstanding -= len(leftover)
            self.errors += len(leftover)
        for _, fut in leftover:
            if not fut.done():
                fut.set_exception(StreamError(
                    f"{self.name}: {cause} before the frame was "
                    f"dispatched"))
        # bounded sentinel enqueue: if the completion stage is wedged
        # (hung D2H) its queue may be full — shutdown must still return
        try:
            self._done_q.put(None, timeout=10)
        except Exception:
            log.warning("dispatcher completion queue wedged at shutdown")
        self._completer.join(timeout=10)
        if self._completer.is_alive():
            log.warning("dispatcher: completer thread %s still alive after "
                        "10s join at shutdown — thread leaked",
                        self._completer.name)

    # -- batcher loop ------------------------------------------------------
    def _loop(self) -> None:
        bucket = self.buckets[-1]
        while True:
            self._wake.wait(timeout=0.1)
            with self._lock:
                if self._stop and not self._pending:
                    return
                have = len(self._pending)
            if have == 0:
                self._wake.clear()
                continue
            if have < bucket:
                # linger briefly for more frames, then flush what we have
                time.sleep(self.max_delay)
            with self._lock:
                take = self._pending[:bucket]
                del self._pending[: len(take)]
                if not self._pending:
                    self._wake.clear()
            if take:
                self._run_batch(take)

    def _squeeze(self, f):
        """Accept samples with or without a leading batch=1 dim."""
        f = np.asarray(f)
        return f[0] if f.ndim > 1 and f.shape[0] == 1 else f

    def _run_batch(self, take) -> None:
        if self.raw:
            self._run_raw(take)
            return
        frames = [self._squeeze(f) for f, _ in take]
        n = len(frames)
        try:
            batch = np.stack(frames, axis=0)
            tgt = next(b for b in self.buckets if b >= n)
            if n < tgt:          # pad to the chosen compiled size
                pad = np.zeros((tgt - n,) + batch.shape[1:], batch.dtype)
                batch = np.concatenate([batch, pad], axis=0)
            out = self._run(batch, n)
            outs = out if isinstance(out, (tuple, list)) else (out,)
            for o in outs:       # start the D2H now; the completion
                start = getattr(o, "copy_to_host_async", None)
                if start is not None:    # thread reads it later
                    try:
                        start()
                    except Exception:
                        pass     # best-effort; np.asarray still correct
            # hand off to the completion stage (bounded: backpressure
            # keeps at most a few batches in flight on device)
            self._done_q.put((outs, take, n))
        except Exception as e:  # resolve futures, never hang clients
            self._fail(take, e)

    def _run_raw(self, take) -> None:
        """Raw-payload batch: one output tuple per payload, no
        stack/pad. The same overlapped-D2H handoff applies — device
        arrays start their host copy here, the completion thread reads
        them back."""
        n = len(take)
        try:
            outs = self._run([p for p, _ in take], n)
            if len(outs) != n:
                raise StreamError(
                    f"raw run returned {len(outs)} results for {n} "
                    f"payloads")
            for per_item in outs:
                for o in per_item:
                    start = getattr(o, "copy_to_host_async", None)
                    if start is not None:
                        try:
                            start()
                        except Exception:
                            pass
            self._done_q.put((outs, take, n))
        except Exception as e:
            self._fail(take, e)

    def abort(self, cause: str = "aborted") -> None:
        """Fence-style teardown: fail every queued-but-undispatched
        payload immediately (the chip is gone — draining would lie),
        let any batch already on device complete, then shut down. The
        caller re-routes the failed payloads to surviving replicas."""
        with self._lock:
            if self._shutdown_done:
                return
            self._stop = True    # refuse new submits before draining
            doomed = self._pending
            self._pending = []
            self.errors += len(doomed)
            self._outstanding -= len(doomed)
        for _, fut in doomed:
            if not fut.done():
                fut.set_exception(StreamError(f"{self.name}: {cause}"))
        self.shutdown(cause)

    def _fail(self, take, e: Exception) -> None:
        with self._lock:
            self.errors += len(take)
            self._outstanding -= len(take)
        for _, fut in take:
            if not fut.done():
                fut.set_exception(
                    StreamError(f"{self.name}: dispatch failed: {e}"))

    def _complete_loop(self) -> None:
        import queue as _q

        sentinel_seen = False
        while True:
            if sentinel_seen:
                # drain anything the batcher enqueued just before the
                # sentinel, then exit — no future may be left hanging
                try:
                    item = self._done_q.get_nowait()
                except _q.Empty:
                    return
            else:
                item = self._done_q.get()
            if item is None:
                sentinel_seen = True
                continue
            outs, take, n = item
            try:
                if self.raw:
                    results = [tuple(np.asarray(o) for o in per_item)
                               for per_item in outs]
                else:
                    host = [np.asarray(o) for o in outs]
                    results = [tuple(h[i] for h in host)
                               for i in range(len(take))]
                # count BEFORE resolving: a caller that observed its
                # result (and then read stats()) must see these frames
                with self._lock:
                    self.frames += n
                    self.batches += 1
                    self._outstanding -= n
                for i, (_, fut) in enumerate(take):
                    fut.set_result(results[i])
            except Exception as e:
                self._fail(take, e)


class MeshDispatcher:
    """Batches single-frame requests onto a dp-sharded jit computation.

    fn(params, x) must accept a leading batch dim; `bucket` is the
    compiled batch size (requests are padded up to it, so there is
    exactly one compilation).
    """

    def __init__(self, fn: Callable, params, mesh: Mesh, *,
                 bucket: int = 8, max_delay_ms: float = 2.0,
                 batch_axis: str = "dp"):
        if bucket % mesh.shape[batch_axis] != 0:
            raise StreamError(
                f"bucket {bucket} must be divisible by mesh axis "
                f"{batch_axis!r} size {mesh.shape[batch_axis]}"
            )
        self.mesh = mesh
        self.bucket = bucket
        self.max_delay = max_delay_ms / 1e3
        x_sharding = NamedSharding(mesh, P(batch_axis))

        def batched(params, x):
            x = jax.lax.with_sharding_constraint(x, x_sharding)
            return fn(params, x)

        self._params = params
        self._fn = jax.jit(batched)
        # compiled batch sizes: a partial flush pads only up to the
        # SMALLEST bucket that fits it — a lone closed-loop frame rides
        # the dp-sized program (1 on a single chip) instead of paying
        # the full bucket's H2D/compute/D2H (jit compiles each size
        # lazily on first use; at most these two shapes exist)
        self._core = BatchCore(
            self._exec, sorted({mesh.shape[batch_axis], bucket}),
            self.max_delay, name="mesh-dispatch")

    def _exec(self, batch: np.ndarray, n: int):
        return self._fn(self._params, jnp.asarray(batch))

    # -- client API --------------------------------------------------------
    def submit(self, frame) -> Future:
        """frame: single-sample array (no batch dim or batch=1)."""
        return self._core.submit(frame)

    def infer(self, frame, timeout: Optional[float] = 30.0):
        return self._core.infer(frame, timeout)

    def set_params(self, params) -> None:
        """Swap the model parameters (hot swap). A plain reference
        assignment: batches already collected keep the params they were
        dispatched with; every later batch sees the new tree. Shapes
        must match the old tree — same compiled program, no retrace."""
        self._params = params

    @property
    def buckets(self) -> List[int]:
        return list(self._core.buckets)

    @property
    def frames(self) -> int:
        return self._core.frames

    @property
    def batches(self) -> int:
        return self._core.batches

    def stats(self) -> dict:
        return self._core.stats()

    def shutdown(self) -> None:
        self._core.shutdown()
