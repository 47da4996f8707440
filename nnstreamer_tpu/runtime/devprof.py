"""Device performance plane: live MFU, cost registry, HBM ledger.

The hardware-level numbers that decide every "as fast as the hardware
allows" question — FLOPs per compiled program, achieved TFLOP/s, MFU
against the chip's peak, HBM residency and headroom — used to exist
only inside bench.py's batch sweep.  This module makes them a *serving*
plane: backends report every compile (XLA cost-model FLOPs + bytes
accessed + compile wall-time, keyed by (filter, bucket)), the
`device_sync` choke point samples invoke durations into per-bucket
reservoirs, and `stats()` folds both into achieved TFLOP/s, MFU,
roofline classification and a per-device HBM ledger that
`serving.metrics.metrics_snapshot(devprof=...)` exports as
``nns_jit_*`` / ``nns_invoke_*`` / ``nns_device_hbm_*`` families.

This is the ONLY blessed home (nnlint NNL010) for XLA cost-model reads
(``lower().cost_analysis()``), device memory ledgers
(``memory_stats()``) and peak-FLOPs/bandwidth tables inside the
package — one accounting site means one place where "peak" and
"achieved" can silently diverge, and the audit rule keeps it that way
(bench.py, outside the package, keeps its own sweep-local copy).

Accounting model
----------------
- **Compile time**: backends call :meth:`DeviceProfiler.capture_cost`
  right after a cache-miss invoke, passing the jitted callable and the
  concrete args.  The profiler re-lowers (no second XLA compile —
  ``Lowered.cost_analysis()`` is an HLO-level estimate) and records
  flops / bytes accessed / compile wall seconds into the cost
  registry.  Compile events are rare by design (bucketed caches), so
  the extra trace+lower never rides the steady-state hot path.
- **Invoke time**: backends mark the dispatch
  (:meth:`DeviceProfiler.note_dispatch`, a thread-local stamp), and the
  next ``device_sync`` on the same thread closes the sample —
  dispatch→sync-complete wall time is the device-time observation,
  taken exactly where the runtime already forces device completion so
  the tracer's forced-sync accounting stays untouched.  Sampling is
  opportunistic (async-mode sinks on another thread simply do not
  sample); *cumulative* invoke seconds per bucket stay exact for the
  samples taken, which is what the proctime reconciliation check uses.
- **MFU**: achieved TFLOP/s = registry flops / median sampled invoke
  seconds.  Against a declared TPU peak that is MFU; on CPU emulation
  (tier-1) there is no meaningful peak, so ``mfu`` reports 0 and
  ``mfu_calibrated`` falls back to the best achieved TFLOP/s observed
  so far as a measured calibration peak — ratios stay comparable
  across buckets even where the absolute denominator is unknowable.
- **Roofline**: arithmetic intensity (flops / bytes accessed) against
  the ridge point (peak flops / peak bandwidth) classifies each bucket
  compute- vs memory-bound; without both peaks the verdict is
  "unknown", never a guess.

Kept dependency-light (stdlib + lazy jax) so `runtime.sync` can import
it without pulling the package graph in.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from collections import deque

#: declared bf16 dense peak TFLOP/s per TPU generation (per chip) —
#: public spec-sheet numbers; the MFU denominator on real hardware
PEAK_TFLOPS = {
    "TPU v2": 45.0,
    "TPU v3": 123.0,
    "TPU v4": 275.0,
    "TPU v4i": 138.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5": 459.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}

#: declared HBM bandwidth GB/s per TPU generation (per chip) — the
#: roofline's memory peak; ridge point = peak flops / peak bandwidth
PEAK_HBM_GBPS = {
    "TPU v2": 700.0,
    "TPU v3": 900.0,
    "TPU v4": 1228.0,
    "TPU v4i": 614.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5": 2765.0,
    "TPU v5p": 2765.0,
    "TPU v6 lite": 1640.0,
    "TPU v6e": 1640.0,
}


def peak_for(device_kind: str) -> Tuple[float, float]:
    """(peak TFLOP/s, peak HBM GB/s) for a jax ``device_kind`` string;
    (0, 0) when the platform has no declared peak (CPU emulation,
    unknown chip) — callers treat 0 as "denominator unknown"."""
    k = str(device_kind or "").strip()
    if k in PEAK_TFLOPS:
        return PEAK_TFLOPS[k], PEAK_HBM_GBPS.get(k, 0.0)
    # longest-prefix match tolerates suffixed kinds ("TPU v4 pod slice")
    best = ""
    for known in PEAK_TFLOPS:
        if k.lower().startswith(known.lower()) and len(known) > len(best):
            best = known
    if best:
        return PEAK_TFLOPS[best], PEAK_HBM_GBPS.get(best, 0.0)
    return 0.0, 0.0


def require_peak(device_kind: str) -> Tuple[float, float]:
    """`peak_for`, for callers that print an MFU or a roofline share: a
    device that is not in the table is an error, not a zero (or another
    chip's) denominator."""
    tflops, gbps = peak_for(device_kind)
    if not tflops or not gbps:
        raise ValueError(
            f"no declared peak for device_kind {device_kind!r}: an MFU or "
            f"roofline figure needs PEAK_TFLOPS / PEAK_HBM_GBPS entries "
            f"(runtime/devprof.py) with their source")
    return tflops, gbps


class DeviceProfiler:
    """Process-wide cost registry + invoke reservoirs + HBM ledger.

    Off by default: every hot-path hook starts with an ``enabled``
    check, so the plane costs one attribute read until something
    (bench's devprof arm, a test) turns it on in the process that owns
    the chip.
    Thread model: registry and reservoirs are dict/deque appends under
    one lock taken only on compile events and sync samples (both
    orders of magnitude rarer than frames); the dispatch stamp is
    thread-local and lock-free.
    """

    def __init__(self, reservoir: int = 128,
                 peak_tflops: Optional[float] = None,
                 peak_hbm_gbps: Optional[float] = None):
        self._lock = threading.Lock()
        self.enabled = False
        self._reservoir = int(reservoir)
        # (filter, bucket) -> {"flops", "bytes_accessed", "compile_s",
        #                      "compiles"} — cumulative, never reset
        self._cost: Dict[Tuple[str, str], Dict[str, float]] = {}
        # (filter, bucket) -> {"ring": deque, "seconds": float,
        #                      "count": int} — ring is the reservoir,
        # seconds/count are exact cumulative totals for reconciliation
        self._invoke: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._tl = threading.local()
        # label -> weakref to a backend exposing resident_bytes()
        self._models: Dict[str, Any] = {}
        self._calib_tflops = 0.0      # best achieved — the CPU "peak"
        self._peak_override = (peak_tflops, peak_hbm_gbps)
        self._device_info: Optional[Dict[str, Any]] = None

    # -- lifecycle ---------------------------------------------------------
    def enable(self, on: bool = True) -> "DeviceProfiler":
        self.enabled = bool(on)
        return self

    def reset(self) -> None:
        """Drop all accounting (tests and bench arms)."""
        with self._lock:
            self._cost.clear()
            self._invoke.clear()
            self._models.clear()
            self._calib_tflops = 0.0
            self._device_info = None
        self._tl = threading.local()

    # -- compile-time capture ----------------------------------------------
    def note_compile(self, filt: str, bucket: str, *, seconds: float,
                     flops: float = 0.0,
                     bytes_accessed: float = 0.0) -> None:
        """Record one compile event into the cost registry.  The
        flops/bytes of a (filter, bucket) key are a property of the
        program, so re-compiles (LRU evictions, swaps) overwrite the
        estimate and accumulate wall seconds."""
        if not self.enabled:
            return
        with self._lock:
            e = self._cost.setdefault(
                (str(filt), str(bucket)),
                {"flops": 0.0, "bytes_accessed": 0.0,
                 "compile_s": 0.0, "compiles": 0})
            if flops:
                e["flops"] = float(flops)
            if bytes_accessed:
                e["bytes_accessed"] = float(bytes_accessed)
            e["compile_s"] += max(0.0, float(seconds))
            e["compiles"] += 1

    def capture_cost(self, filt: str, bucket: str, jitted: Any,
                     args: tuple, *, seconds: float,
                     kwargs: Optional[dict] = None) -> None:
        """Compile-event hook for backends: re-lower ``jitted`` over the
        concrete ``args`` (+ ``kwargs`` for static argnames) and harvest
        the XLA cost model (flops, bytes accessed).  Lowering is
        trace-level work — no second device compile — and only runs on
        cache misses.  Any failure (abstract args, exotic backend)
        degrades to a seconds-only entry."""
        if not self.enabled:
            return
        flops = bytes_accessed = 0.0
        try:
            cost = jitted.lower(*args, **(kwargs or {})) \
                .cost_analysis() or {}
            if isinstance(cost, (list, tuple)):   # per-computation form
                cost = cost[0] if cost else {}
            flops = float(cost.get("flops", 0.0) or 0.0)
            bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0)
        except Exception:
            pass
        self.note_compile(filt, bucket, seconds=seconds, flops=flops,
                          bytes_accessed=bytes_accessed)

    # -- invoke-time sampling ----------------------------------------------
    def note_dispatch(self, filt: str, bucket: str,
                      t0: Optional[float] = None) -> None:
        """Stamp this thread's in-flight dispatch; the next
        ``device_sync`` on the same thread closes the sample."""
        if not self.enabled:
            return
        self._tl.pending = (str(filt), str(bucket),
                            time.perf_counter() if t0 is None else t0)

    def sample_sync(self, t_end: Optional[float] = None) -> None:
        """Close the pending dispatch stamp (called from
        ``runtime.sync.device_sync`` right after the block completes).
        No pending stamp on this thread → no sample; the reservoirs are
        a sampling plane, not an accounting ledger."""
        if not self.enabled:
            return
        pending = getattr(self._tl, "pending", None)
        if pending is None:
            return
        self._tl.pending = None
        filt, bucket, t0 = pending
        end = time.perf_counter() if t_end is None else t_end
        if end > t0:
            self.note_invoke(filt, bucket, end - t0)

    def note_invoke(self, filt: str, bucket: str, seconds: float) -> None:
        """Record one sampled device-time observation."""
        if not self.enabled or seconds <= 0:
            return
        with self._lock:
            e = self._invoke.setdefault(
                (str(filt), str(bucket)),
                {"ring": deque(maxlen=self._reservoir),
                 "seconds": 0.0, "count": 0})
            e["ring"].append(float(seconds))
            e["seconds"] += float(seconds)
            e["count"] += 1

    # -- HBM ledger ---------------------------------------------------------
    def attach_model(self, label: str, backend: Any) -> None:
        """Register a backend for per-model HBM attribution: its
        ``resident_bytes()`` (and ``resident_bytes_by_version()`` when
        present) show up as ``model:<label>`` rows in the ledger.  Held
        by weakref — a released model silently leaves the ledger."""
        if not label:
            return
        with self._lock:
            self._models[str(label)] = weakref.ref(backend)

    def _device_meta(self) -> Dict[str, Any]:
        """Platform/device-kind/count, cached after first read (device
        topology does not change mid-process). A backend that cannot
        initialise raises: the profile names the device it measured."""
        if self._device_info is None:
            import jax

            devs = jax.devices()
            self._device_info = {"platform": devs[0].platform,
                                 "device_kind": devs[0].device_kind,
                                 "devices": len(devs)}
        return self._device_info

    def hbm_rows(self) -> List[Dict[str, Any]]:
        """Per-device memory ledger rows {device, kind, bytes} from
        ``memory_stats()`` (absent on CPU emulation — rows simply do
        not appear), plus ``model:<label>`` attribution rows from
        attached backends."""
        rows: List[Dict[str, Any]] = []
        try:
            import jax

            for d in jax.devices():
                try:
                    ms = d.memory_stats()
                except Exception:
                    ms = None
                if not ms:
                    continue
                dev = f"{d.platform}:{d.id}"
                for kind in ("bytes_in_use", "bytes_limit",
                             "peak_bytes_in_use"):
                    if kind in ms:
                        rows.append({"device": dev, "kind": kind,
                                     "bytes": float(ms[kind])})
        except Exception:
            pass
        with self._lock:
            models = list(self._models.items())
        for label, ref in models:
            be = ref()
            if be is None:
                with self._lock:
                    self._models.pop(label, None)
                continue
            try:
                by_ver = getattr(be, "resident_bytes_by_version", None)
                vers = by_ver() if by_ver is not None else None
                if vers:
                    for v, b in sorted(vers.items()):
                        rows.append({"device": "-",
                                     "kind": f"model:{label}@{v}",
                                     "bytes": float(b)})
                else:
                    rows.append({"device": "-", "kind": f"model:{label}",
                                 "bytes": float(be.resident_bytes())})
            except Exception:
                continue
        return rows

    # -- read-out -----------------------------------------------------------
    def _peaks(self) -> Tuple[float, float]:
        ot, ob = self._peak_override
        if ot is not None:
            return float(ot), float(ob or 0.0)
        meta = self._device_meta()
        return peak_for(meta["device_kind"])

    def stats(self) -> Dict[str, Any]:
        """One coherent snapshot for the metrics plane / top / bundles:
        ``jit`` rows (cost registry), ``invoke`` rows (reservoir-derived
        achieved TFLOP/s + MFU + cumulative seconds), ``hbm`` +
        ``headroom`` rows, and the peak table actually applied."""
        meta = self._device_meta()
        peak_tf, peak_bw = self._peaks()
        ridge = (peak_tf * 1e12) / (peak_bw * 1e9) if peak_tf and peak_bw \
            else 0.0
        with self._lock:
            cost = {k: dict(v) for k, v in self._cost.items()}
            invoke = {k: {"samples": list(v["ring"]),
                          "seconds": v["seconds"], "count": v["count"]}
                      for k, v in self._invoke.items()}
        jit_rows = []
        for (filt, bucket), e in sorted(cost.items()):
            ai = e["flops"] / e["bytes_accessed"] \
                if e["bytes_accessed"] else 0.0
            if not e["flops"] or not ridge:
                roofline = "unknown"
            else:
                roofline = "compute" if ai >= ridge else "memory"
            jit_rows.append({
                "filter": filt, "bucket": bucket,
                "flops": e["flops"],
                "bytes_accessed": e["bytes_accessed"],
                "compile_s": e["compile_s"], "compiles": e["compiles"],
                "ai": round(ai, 3), "roofline": roofline,
            })
        # calibration peak: best achieved TFLOP/s across every bucket —
        # the measured denominator where no declared peak exists
        achieved: Dict[Tuple[str, str], float] = {}
        for key, e in invoke.items():
            samples = sorted(e["samples"])
            if not samples:
                continue
            med = samples[len(samples) // 2]
            flops = cost.get(key, {}).get("flops", 0.0)
            tf = flops / med / 1e12 if flops and med > 0 else 0.0
            achieved[key] = (tf, med)
            if tf > self._calib_tflops:
                self._calib_tflops = tf
        invoke_rows = []
        for (filt, bucket), e in sorted(invoke.items()):
            tf, med = achieved.get((filt, bucket), (0.0, 0.0))
            invoke_rows.append({
                "filter": filt, "bucket": bucket,
                "device": meta["device_kind"],
                "seconds_total": e["seconds"],
                "samples_total": e["count"],
                "p50_ms": round(med * 1e3, 4),
                "achieved_tflops": round(tf, 4),
                "mfu": round(tf / peak_tf, 4) if peak_tf else 0.0,
                "mfu_calibrated": round(tf / self._calib_tflops, 4)
                if self._calib_tflops else 0.0,
            })
        hbm = self.hbm_rows()
        headroom = []
        by_dev: Dict[str, Dict[str, float]] = {}
        for r in hbm:
            if r["device"] != "-":
                by_dev.setdefault(r["device"], {})[r["kind"]] = r["bytes"]
        for dev, kinds in sorted(by_dev.items()):
            limit = kinds.get("bytes_limit", 0.0)
            if limit:
                headroom.append({
                    "device": dev,
                    "frac": round(kinds.get("bytes_in_use", 0.0) / limit,
                                  6)})
        return {
            "enabled": self.enabled,
            "platform": meta["platform"],
            "device_kind": meta["device_kind"],
            "devices": meta["devices"],
            "peak_tflops": peak_tf,
            "peak_hbm_gbps": peak_bw,
            "calibration_tflops": round(self._calib_tflops, 4),
            "compile_seconds_total": round(
                sum(r["compile_s"] for r in jit_rows), 6),
            "compiles_total": sum(r["compiles"] for r in jit_rows),
            "jit": jit_rows,
            "invoke": invoke_rows,
            "hbm": hbm,
            "headroom": headroom,
        }

    def counter_tracks(self) -> List[Tuple[str, float]]:
        """(name, value) counter samples for Chrome-trace counter
        tracks: per-bucket MFU (calibrated where no declared peak) and
        per-device HBM in-use."""
        st = self.stats()
        out: List[Tuple[str, float]] = []
        for r in st["invoke"]:
            v = r["mfu"] if st["peak_tflops"] else r["mfu_calibrated"]
            out.append((f"mfu:{r['filter']}/{r['bucket']}", v))
        for r in st["hbm"]:
            if r["kind"] == "bytes_in_use":
                out.append((f"hbm:{r['device']}", r["bytes"]))
        return out


#: process-wide profiler — backends and `device_sync` all talk to this
#: one instance; off until something enables it
_PROFILER = DeviceProfiler()


def get() -> DeviceProfiler:
    return _PROFILER


def bucket_label(basekey: tuple) -> str:
    """Compact bounded-cardinality label for a backend bucket key:
    ``("fix", ((1, 224, 224, 3), "uint8"), ...)`` → ``fix:1x224x224x3``,
    ``("dynb", 8, ...)`` → ``dynb:8``.  Cardinality is bounded by the
    backend's own bucketing (pow2 batches, served-shape set)."""
    if not basekey:
        return "static"
    kind = str(basekey[0])
    if kind == "fix" and len(basekey) > 1:
        shape = basekey[1][0] if isinstance(basekey[1], tuple) else ()
        return f"fix:{'x'.join(str(d) for d in shape)}"
    if kind == "dynb" and len(basekey) > 1:
        return f"dynb:{basekey[1]}"
    return kind
