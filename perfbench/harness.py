"""The parts of a run that do not depend on what is served: the manifest,
the chip, the load loop, the profiler window, the per-layer readers and the
result line."""

from __future__ import annotations

import faulthandler
import importlib
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

from perfbench import xplane
from perfbench.traffic import Arrival

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".perfbench_trace")


class HarnessError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, ...)."""


# -- manifest -----------------------------------------------------------------

def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]      # manifest entries this cell reports
    per_layer: List[dict]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(manifest: dict, workload: str, root: str = ROOT) -> Cell:
    """Find the cell's files by the names in the manifest."""
    from perfbench import traffic as traffic_mod

    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise HarnessError(
            f"no workload {workload!r} in BENCHMARK.json "
            f"(known: {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    e2e = [m for m in manifest["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if _reports(m, workload) and m["moves"] in moved]
    return Cell(workload, int(w["chips"]), w["config"], w["traffic"], config,
                traffic_mod.load(w["traffic"]), e2e, layer)


# -- the chip -----------------------------------------------------------------

def require_chip(chips: int) -> dict:
    """The device as JAX reports it; fails unless it is `chips` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise HarnessError(
            f"no accelerator: JAX reports platform {devs[0].platform!r}; "
            f"the benchmark measures on a TPU only")
    if len(devs) < chips:
        raise HarnessError(
            f"the cell asks for {chips} chips and JAX finds {len(devs)}")
    return device_info(devs[:chips])


def device_info(devs: Sequence) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs: Sequence) -> int:
    peak = 0
    for d in devs:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


# -- set-up phases --------------------------------------------------------------

class Phases:
    """Named wall-clock phases of set-up, printed on a line of their own."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.seconds: Dict[str, float] = {}
        self._last = t_start

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now

    def mark_at(self, name: str, t: float) -> None:
        """Close the phase `name` at the instant `t` (which may lie ahead)."""
        self.seconds[name] = self.seconds.get(name, 0.0) + t - self._last
        self._last = t

    def line(self) -> str:
        return "setup_phases " + json.dumps(
            {k: round(v, 3) for k, v in self.seconds.items()})


# -- the load loop ----------------------------------------------------------------

def drive(arrivals: List[Arrival], t0: float, send: Callable[[Arrival], None],
          until: Optional[float] = None) -> List[float]:
    """Open loop: send each arrival when it is due (``t0 + due_s`` on
    ``time.perf_counter``), never earlier, and return how late each was
    sent, in seconds.  Runs in the calling thread; sleeps between
    arrivals.  `until` (absolute) ends the loop early."""
    lag: List[float] = []
    for a in arrivals:
        due = t0 + a.due_s
        while True:
            now = time.perf_counter()
            if until is not None and now >= until:
                return lag
            wait = due - now
            if wait <= 0:
                break
            time.sleep(wait if wait < 0.002 else wait - 0.001)
        send(a)
        lag.append(time.perf_counter() - due)
    return lag


def stop_and_join(owner) -> None:
    """Stop `owner.runner` (a PipelineRunner), keep its first error in
    `owner.pipeline_error`, and wait for its threads to end."""
    runner = owner.runner
    runner.stop()
    if owner.pipeline_error is None:
        owner.pipeline_error = getattr(runner, "_error", None)
    try:
        runner.wait(10.0)
    except Exception:       # the error is kept above; a late thread is a
        pass                # daemon and ends with the process


def sleep_until(t: float) -> None:
    while True:
        wait = t - time.perf_counter()
        if wait <= 0:
            return
        time.sleep(min(wait, 0.05))


class StallWatch:
    """Says on the error stream where every thread stands when the served
    path has answered nothing for `limit_s`: `last()` is the instant of
    its newest answer.  A benchmark cannot cure a stall of the program,
    but a run that had one should show where it was.  Five looks a
    second from a thread of its own; it also says so when it was itself
    not run, which means the whole process stood still, and how much CPU
    the process used meanwhile."""

    def __init__(self, last: Callable[[], float], limit_s: float = 1.5):
        self.last, self.limit_s = last, limit_s
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="perfbench-stall", daemon=True)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()

    def _run(self) -> None:
        told, tick, cpu = -1.0, time.perf_counter(), time.process_time()
        while not self._stop.wait(0.2):
            now, last = time.perf_counter(), self.last()
            if now - tick > 1.0:
                # CPU time near the gap: a thread ran and kept the
                # interpreter to itself; near none: the process was not run
                warn(f"stall: the watcher itself was not run for "
                     f"{now - tick:.1f} s: the whole process stood still "
                     f"(its threads used {time.process_time() - cpu:.1f} s "
                     f"of CPU meanwhile)")
            tick, cpu = now, time.process_time()
            if now - last > self.limit_s and last != told:
                told = last
                warn(f"stall: nothing answered for {now - last:.1f} s; "
                     f"every thread's stack follows")
                faulthandler.dump_traceback(file=sys.stderr, all_threads=True)


# -- the profiler window ------------------------------------------------------------

class TraceWindow:
    """Profile a stretch of the measured window from a helper thread, so
    that the load loop keeps its schedule.  After ``join()``: ``trace``
    (an ``xplane.Trace``), ``start``/``end`` on the trace's clock, and
    ``to_trace(t)`` mapping ``time.perf_counter`` onto it."""

    def __init__(self, begin: float, seconds: float):
        self.begin, self.seconds = begin, seconds
        self.trace: Optional[xplane.Trace] = None
        self.start = self.end = 0.0
        self._pc_marker = 0.0
        self._pc_end = 0.0
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run,
                                        name="perfbench-trace", daemon=True)

    def launch(self) -> None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        self._thread.start()

    def _run(self) -> None:
        import jax.profiler as jp

        try:
            sleep_until(self.begin)
            opts = jp.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jp.start_trace(TRACE_DIR, profiler_options=opts)
            try:
                with jp.TraceAnnotation(xplane.MARKER):
                    self._pc_marker = time.perf_counter()
                sleep_until(self._pc_marker + self.seconds)
                self._pc_end = time.perf_counter()
            finally:
                jp.stop_trace()
        except BaseException as e:      # re-raised by join()
            self._error = e

    def join(self) -> None:
        self._thread.join()
        if self._error is not None:
            raise self._error
        self.trace = xplane.read_xplane(xplane.find_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        marker = xplane.marker_time(self.trace)
        if marker is None:
            raise HarnessError("the clock marker is not in the trace")
        self.start = marker
        self.end = marker + (self._pc_end - self._pc_marker)

    def to_trace(self, t: float) -> float:
        return self.start + (t - self._pc_marker)

    def in_window(self, t: float) -> bool:
        return self._pc_marker <= t <= self._pc_end


# -- checks and the result line ---------------------------------------------------------

@dataclass
class Check:
    """One number compared, beside its limit."""
    name: str
    value: float
    limit: float
    ok: bool

    def line(self) -> str:
        return (f"check {self.name}: {self.value!r} limit {self.limit!r} "
                f"({'ok' if self.ok else 'FAILED'})")


def at_most(name: str, value: float, limit: float) -> Check:
    return Check(name, value, limit, bool(value <= limit))


@dataclass
class Outcome:
    """What a runner hands back after its window."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]             # name -> value
    checks: List[Check]
    readings: dict = field(default_factory=dict)   # for the layer readers


def read_layer_metrics(cell: Cell, ctx: dict) -> Dict[str, dict]:
    """Each per-layer metric of the cell through its own reader,
    ``perfbench/layer_metrics/<base>.py`` where the metric is named
    ``<base>.<tag>``.  A reader that finds nothing returns None and the
    metric is left out."""
    out: Dict[str, dict] = {}
    for m in cell.per_layer:
        base = m["name"].rsplit(".", 1)[0]
        mod = importlib.import_module(f"perfbench.layer_metrics.{base}")
        value = mod.read(dict(ctx, metric=m))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(correct: bool, outcome: Outcome, metrics: Dict[str, dict],
                device: dict, breakdown: Optional[dict]) -> str:
    obj = {"correct": bool(correct), "attempted": int(outcome.attempted),
           "failed": int(outcome.failed), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        obj["breakdown"] = breakdown
    return json.dumps(obj)


def window_rate_line(times: Sequence[float], t0: float, seconds: float) -> str:
    """How many events (tokens, frames) each second of the window saw:
    a line for the reader of a run, not a metric."""
    n = max(1, int(seconds + 0.999))
    per = [0] * n
    for t in times:
        if t0 <= t < t0 + seconds:
            per[min(n - 1, int(t - t0))] += 1
    return "window_rate " + json.dumps(per)


def log(msg: str) -> None:
    print(msg, flush=True)


def warn(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
