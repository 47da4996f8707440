"""What the runners share: the cell's settings, the program's tracer, the
opening of a window and the reading of the tracer's spans."""

from __future__ import annotations

import importlib
import time
from typing import Callable, List, Optional, Tuple

from perfbench import harness, traffic


# The profiled stretch in the middle of a traced window.  It has to hold
# several runs of every program a reader looks for: the chat backlog admits
# a request (one prefill) about every 0.65 s, and 2 s held none in some runs.
TRACE_S = 6.0


class RunnerBase:
    def __init__(self, cell: harness.Cell, seed: int, seconds: float,
                 trace: bool, devices):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.devices = trace, devices
        self.cfg = cell.config
        self.serving = self.cfg["serving"]
        self.ref = importlib.import_module(
            f"perfbench.references.{self.cfg['reference']}")
        self.model_name = "perfbench_" + cell.config_name.replace(".", "_")
        self.params = None
        self.runner = None
        self.pipeline_error: Optional[BaseException] = None

    def _tracer(self):
        """The program's tracer for a traced run, large enough that the
        traced stretch is still in its ring when the window closes."""
        if not self.trace:
            return False
        from nnstreamer_tpu.runtime.tracing import Tracer

        return Tracer(max_events=1 << 21)

    def _raise_if_failed(self) -> None:
        err = getattr(self.runner, "_error", None)
        if err is not None:
            raise harness.HarnessError(f"the pipeline failed: {err!r}")

    def _open_window(self, phases: harness.Phases
                     ) -> Tuple[List[traffic.Arrival], float,
                                Optional[harness.TraceWindow]]:
        """The schedule, the window's first instant (after the ramp), and
        the profiler window in its middle when the run is traced."""
        tr = self.cell.traffic
        arrivals = traffic.schedule(tr, self.seed, self.seconds)
        ramp = float(tr["arrival"]["ramp_s"])
        t0 = time.perf_counter() + ramp + 0.05
        phases.mark_at("ramp", t0)
        tw = None
        if self.trace:
            span = min(TRACE_S, 0.5 * self.seconds)
            tw = harness.TraceWindow(t0 + 0.5 * (self.seconds - span), span)
            tw.launch()
        return arrivals, t0, tw

    def _tracer_events(self) -> list:
        return self.runner.tracer.events() if self.trace else []

    @staticmethod
    def _host_spans(obs: dict, on_backend: Callable[[str, float, dict], None]
                    ) -> List[tuple]:
        """The tracer's spans inside the profiled stretch, on the trace's
        clock; `on_backend(label, t, args)` sees each backend span."""
        tw = obs["tw"]
        spans = []
        for ph, cat, name, label, ts, dur, args in obs["tracer_events"]:
            if ph != "X" or not tw.in_window(ts):
                continue
            spans.append((f"{cat}:{name}:{label}", tw.to_trace(ts), dur))
            if cat == "backend" and args:
                on_backend(label, ts, args)
        return spans
