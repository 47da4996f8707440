"""Helpers shared by the readers of the program's own spans (source
``program_span``).  ``ctx["host_spans"]`` holds the tracer's spans of the
traced stretch as ``("cat:name:label", start, seconds)`` on the trace's
clock; a reader tells spans apart by `cat` and `label`, under any element
name.

A *step* is an ``element:<name>:timer`` span that holds a
``backend:<name>:dispatch`` span: one serving quantum of ``tensor_llm``
that launched device work.  A program without these child spans has no
step here, and the readers then report nothing."""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

EPS = 1e-6      # seconds; spans of one thread on one clock nest exactly


def split(key: str) -> Tuple[str, str, str]:
    """``"cat:name:label"`` -> (cat, name, label); a name may hold colons."""
    cat, rest = key.split(":", 1)
    name, label = rest.rsplit(":", 1)
    return cat, name, label


def count(ctx: dict, cat: str, prefix: str) -> Dict[str, int]:
    """How many spans of `cat` the stretch holds under each label that
    starts with `prefix`."""
    out: Dict[str, int] = {}
    for key, _, _ in ctx.get("host_spans") or ():
        c, _, label = split(key)
        if c == cat and label.startswith(prefix):
            out[label] = out.get(label, 0) + 1
    return out


def steps(ctx: dict) -> List[Dict[str, float]]:
    """One entry for each step that lies wholly inside the stretch:
    ``{"timer": seconds of the step, <label>: summed seconds of the
    backend spans and the emit span of that label inside it}``."""
    tw = ctx.get("trace_window")
    end = tw.end if tw is not None else float("inf")
    timers: Dict[str, List[Tuple[float, float]]] = {}
    children = []
    for key, start, dur in ctx.get("host_spans") or ():
        cat, name, label = split(key)
        if cat == "element" and label == "timer":
            if start + dur <= end + EPS:
                timers.setdefault(name, []).append((start, start + dur))
        elif cat == "backend" or (cat, label) == ("element", "emit"):
            children.append((name, label, start, dur))
    found: Dict[Tuple[str, float], Dict[str, float]] = {}
    for spans in timers.values():
        spans.sort()
    for name, label, start, dur in children:
        spans = timers.get(name)
        if not spans:
            continue
        i = bisect.bisect_right(spans, (start + EPS, float("inf"))) - 1
        if i < 0 or start + dur > spans[i][1] + EPS:
            continue
        t0, t1 = spans[i]
        step = found.setdefault((name, t0), {"timer": t1 - t0})
        step[label] = step.get(label, 0.0) + dur
    return [s for s in found.values() if "dispatch" in s]


def ms_per_step(ctx: dict, *labels: str) -> Optional[float]:
    """Milliseconds a step spends under `labels`, summed; None where the
    stretch holds no step."""
    found = steps(ctx)
    if not found:
        return None
    total = sum(s.get(label, 0.0) for s in found for label in labels)
    return 1e3 * total / len(found)
