"""Host-sync choke point.

Every place the runtime must *force* device work to completion (a sink
draining results, a filter in latency_mode=sync, backend warm-up) goes
through :func:`device_sync` instead of hand-rolled per-leaf
``block_until_ready`` loops.  One call site means:

- one whole-tuple ``jax.block_until_ready`` (a single runtime round-trip
  instead of a Python loop over leaves),
- the tracer can count *forced* syncs — the host-path tax the async
  dispatch work exists to remove — as the ``forced_syncs`` stat, and
- the device profiler (runtime/devprof.py) can close its per-bucket
  device-time samples exactly where device completion is forced,
  without its own sync or any change to the forced-sync accounting.

Kept free of package-internal imports (scheduler, filter, sinks and the
XLA backend all call in here) — devprof is the one exception, itself a
stdlib-only leaf — and of an import-time jax dependency.
"""

from __future__ import annotations

import threading
import time

from nnstreamer_tpu.runtime import devprof

_lock = threading.Lock()
_forced = 0


def forced_sync_count() -> int:
    """Process-wide number of forced host syncs since import."""
    return _forced


def device_sync(tensors, tracer=None, name=None, forced=True):
    """Block until every device array in ``tensors`` is resolved.

    ``tensors`` is any pytree-ish container (the usual case: a buffer's
    tensor tuple).  If nothing in it is a device array this is free and
    neither counted nor traced.  Returns ``tensors`` unchanged — device
    results resolve in place.

    ``forced=True`` marks a *semantic* sync (sink, sync latency mode,
    warm-up) and is counted + traced; ``forced=False`` marks window
    backpressure (the bounded in-flight drain), which is expected
    steady-state behavior and only surfaces via the caller's gauge.
    """
    global _forced
    leaves = tensors if isinstance(tensors, (tuple, list)) else (tensors,)
    if not any(hasattr(t, "block_until_ready") for t in leaves):
        return tensors
    import jax

    jax.block_until_ready(tuple(leaves))
    prof = devprof.get()
    if prof.enabled:
        prof.sample_sync()
    if forced:
        with _lock:
            _forced += 1
        if tracer is not None and getattr(tracer, "active", False):
            tracer.record_forced_sync(name or "?", time.perf_counter())
    return tensors
