"""Double-buffered host→device input staging.

The reference's filter path hands each frame to the framework
synchronously (`gst/nnstreamer/tensor_filter/tensor_filter.c` chain
function: map buffer → invoke → unmap); any H2D copy serializes with
compute. On TPU the equivalent naive loop leaves the chip idle for the
whole transfer. The TPU-first design streams instead:
`jax.device_put` is asynchronous, so staging batch N+1 can ride the DMA
engines while batch N computes. This module provides that overlap as a
reusable component:

- `prefetch_to_device(it, depth)` — wrap any host-batch iterator; a
  background thread issues `device_put` up to `depth` batches ahead and
  a bounded queue provides backpressure.
- `DeviceFeeder` — push-style variant for the streaming pipeline: the
  scheduler thread calls `put(host_batch)` (non-blocking up to the
  buffer depth) and the compute side calls `get()`.

Used by `bench.py`'s batch sweep (pipelined-H2D measurement); designed
as the staging layer for batched offload serving (`QueryServer` +
`MeshDispatcher`).
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional

__all__ = ["prefetch_to_device", "DeviceFeeder"]

_STOP = object()


def _default_put(x, device):
    import jax

    if device is None:
        return jax.device_put(x)
    return jax.device_put(x, device)


def prefetch_to_device(it: Iterable[Any], depth: int = 2,
                       device: Any = None,
                       put: Optional[Callable[[Any, Any], Any]] = None
                       ) -> Iterator[Any]:
    """Yield device arrays for each host batch in `it`, staging up to
    `depth` batches ahead of the consumer.

    `put` overrides the transfer function (e.g. a sharded device_put
    with a NamedSharding for multi-chip feeds). Exceptions from the
    source iterator or the transfer re-raise at the consumer.
    """
    if depth < 1:
        raise ValueError(f"prefetch depth must be >= 1, got {depth}")
    put = put or _default_put
    q: "queue.Queue[Any]" = queue.Queue(maxsize=depth)
    cancelled = threading.Event()

    def worker():
        try:
            for x in it:
                staged = put(x, device)
                # device_put is async: the DMA overlaps the consumer's
                # compute on the previous batch. Bounded put so an
                # abandoned consumer doesn't pin this thread (and its
                # staged device buffers) forever.
                while not cancelled.is_set():
                    try:
                        q.put(staged, timeout=0.2)
                        break
                    except queue.Full:
                        continue
                if cancelled.is_set():
                    return
            item = _STOP
        except BaseException as e:      # surface at the consumer side
            item = e
        while not cancelled.is_set():
            try:
                q.put(item, timeout=0.2)
                return
            except queue.Full:
                continue

    t = threading.Thread(target=worker, name="device-prefetch",
                         daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is _STOP:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        # consumer closed the generator (break / exception): release the
        # worker and drop staged buffers
        cancelled.set()


class DeviceFeeder:
    """Push-style double buffer between a producer thread (pipeline
    scheduler / query server) and the device compute loop.

    put() stages the host batch onto the device immediately (async DMA)
    and enqueues the device array; it blocks only when `depth` staged
    batches are already waiting — that backpressure bounds device-memory
    use. get() returns the next staged batch (blocking), so the compute
    loop always finds its input already on-chip.
    """

    def __init__(self, depth: int = 2, device: Any = None,
                 put: Optional[Callable[[Any, Any], Any]] = None):
        if depth < 1:
            raise ValueError(f"feeder depth must be >= 1, got {depth}")
        # one extra slot is reserved for the close() sentinel so closing
        # never blocks behind staged batches; the semaphore keeps data
        # occupancy at `depth`
        self._q: "queue.Queue[Any]" = queue.Queue(maxsize=depth + 1)
        self._slots = threading.BoundedSemaphore(depth)
        self._device = device
        self._put = put or _default_put
        self._closed = False
        # serializes the closed-check with the enqueue so a concurrent
        # close() cannot slip its sentinel between them (which would
        # silently drop the racing batch behind EOS)
        self._lock = threading.Lock()

    def put(self, host_batch: Any, timeout: Optional[float] = None) -> None:
        if self._closed:      # cheap fast path (re-checked under lock)
            raise RuntimeError("DeviceFeeder is closed")
        if not self._slots.acquire(timeout=timeout):
            raise queue.Full("DeviceFeeder staging buffer is full")
        try:
            staged = self._put(host_batch, self._device)
            with self._lock:
                if self._closed:
                    raise RuntimeError("DeviceFeeder is closed")
                self._q.put(staged)  # nnlint: disable=NNL003 non-blocking by invariant: _slots caps data at depth, maxsize=depth+1
        except BaseException:
            self._slots.release()
            raise

    def close(self) -> None:
        """Signal end of stream; get() returns None after draining."""
        with self._lock:
            if not self._closed:
                self._closed = True
                self._q.put(_STOP)  # nnlint: disable=NNL003 non-blocking by invariant: the +1 queue slot is reserved for this sentinel

    def get(self, timeout: Optional[float] = None) -> Optional[Any]:
        item = self._q.get(timeout=timeout)
        if item is _STOP:
            self._q.put(_STOP)      # keep returning None for late gets
            return None
        self._slots.release()
        return item

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
