"""Reduce a profiler trace to the few things the metrics need.

``read_xplane`` reads a ``.xplane.pb`` with nothing but JAX and returns a
plain ``Trace``: per device the program runs (the ``XLA Modules`` line) and
the operations (``XLA Ops``), the host's annotations, all in seconds on the
trace's own clock.  ``Trace`` round-trips through JSON, so a small recorded
trace can sit in ``perfbench/data`` and pin the reduction in a test.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from perfbench import stats

MARKER = "perfbench_clock_marker"
Span = Tuple[str, float, float]          # (name, start_s, duration_s)


@dataclass
class Trace:
    modules: Dict[str, List[Span]] = field(default_factory=dict)
    ops: Dict[str, List[Span]] = field(default_factory=dict)
    host: List[Span] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"modules": self.modules, "ops": self.ops, "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        conv = lambda rows: [(str(n), float(s), float(t)) for n, s, t in rows]
        return cls({k: conv(v) for k, v in d["modules"].items()},
                   {k: conv(v) for k, v in d["ops"].items()},
                   conv(d.get("host", [])))


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dst = tr.modules.setdefault(name, [])
                elif line.name == "XLA Ops":
                    dst = tr.ops.setdefault(name, [])
                else:
                    continue
                for ev in line.events:
                    dst.append((ev.name, ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9))
        elif name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("perfbench_"):
                        tr.host.append((ev.name, ev.start_ns * 1e-9,
                                        ev.duration_ns * 1e-9))
    return tr


def marker_time(tr: Trace) -> Optional[float]:
    """Trace time of the clock marker the harness annotated."""
    for name, start, _ in tr.host:
        if name == MARKER:
            return start
    return None


def module_base(name: str) -> str:
    """``jit_paged_decode_step(1234)`` -> ``jit_paged_decode_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_base(name: str) -> str:
    """``%fusion.2169 = s32[8]{0} fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def _inside(spans: List[Span], start: float, end: float) -> List[Span]:
    return [(n, s, d) for n, s, d in spans if s >= start and s + d <= end]


def busy_seconds(tr: Trace, start: float, end: float) -> float:
    """Seconds in [start, end) in which an operation ran, averaged over
    the devices in the trace."""
    if not tr.ops:
        return 0.0
    total = 0.0
    for spans in tr.ops.values():
        clipped = [(max(s, start), min(s + d, end)) for _, s, d in spans
                   if s < end and s + d > start]
        total += stats.union_length(clipped)
    return total / len(tr.ops)


def idle_gaps(tr: Trace, start: float, end: float) -> List[Tuple[float, float]]:
    """Idle intervals of the busiest-first device inside [start, end)."""
    if not tr.ops:
        return [(start, end)]
    spans = next(iter(tr.ops.values()))
    return stats.gaps([(s, s + d) for _, s, d in spans], start, end)


def module_time(tr: Trace, prefix: str, start: float,
                end: float) -> Tuple[int, float]:
    """(number of runs, summed device seconds) of the programs whose name
    starts with `prefix`, runs wholly inside [start, end), all devices."""
    n, total = 0, 0.0
    for spans in tr.modules.values():
        for name, _, d in _inside(spans, start, end):
            if module_base(name).startswith(prefix):
                n += 1
                total += d
    return n, total


def top_device_ops(tr: Trace, start: float, end: float,
                   limit: int = 10) -> List[List]:
    """The programs and operation kinds that took most device time."""
    mods: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    for spans in tr.modules.values():
        for name, _, d in _inside(spans, start, end):
            key = "program:" + module_base(name)
            mods[key] = mods.get(key, 0.0) + d
    for spans in tr.ops.values():
        for name, _, d in _inside(spans, start, end):
            key = "op:" + op_base(name)
            ops[key] = ops.get(key, 0.0) + d
    top = sorted(mods.items(), key=lambda kv: -kv[1])[:limit // 2 - 1]
    top += sorted(ops.items(), key=lambda kv: -kv[1])[:limit - len(top)]
    return [[k, v] for k, v in top]


def attribute_gaps(gaps: List[Tuple[float, float]], host_spans: List[Span],
                   limit: int = 10) -> List[List]:
    """Idle seconds by what the host was doing: each gap goes to the
    shortest host span that covers its midpoint (``nothing_recorded``
    where none does), summed by name, largest first."""
    by: Dict[str, float] = {}
    spans = sorted(host_spans, key=lambda x: x[1])
    nxt, open_spans = 0, []         # one sweep: gaps and spans in time order
    for g0, g1 in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = 0.5 * (g0 + g1)
        while nxt < len(spans) and spans[nxt][1] <= mid:
            open_spans.append(spans[nxt])
            nxt += 1
        open_spans = [sp for sp in open_spans if sp[1] + sp[2] >= mid]
        best = min(open_spans, key=lambda sp: sp[2], default=None)
        key = best[0] if best else "nothing_recorded"
        by[key] = by.get(key, 0.0) + (g1 - g0)
    return [[k, v] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:limit]]
