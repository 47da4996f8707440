"""Helpers shared by the per-layer readers.  A reader is ``read(ctx)``:
it takes what it needs from the run's readings and returns a number, or
None where there is nothing to read."""

from __future__ import annotations

from typing import Optional

from perfbench import costs, stats, xplane


def delta(ctx: dict, key: str) -> Optional[float]:
    """How far the program's counter `key` moved inside the window."""
    snap = ctx.get("counters") or {}
    if "start" not in snap or "end" not in snap:
        return None
    if key not in snap["start"] or key not in snap["end"]:
        return None
    return snap["end"][key] - snap["start"][key]


def ratio(ctx: dict, num: str, den: str) -> Optional[float]:
    n, d = delta(ctx, num), delta(ctx, den)
    return n / d if n is not None and d else None


def roofline(ctx: dict, kernel: str) -> Optional[float]:
    """Share (%) of its roofline the device program of `kernel` reached
    in the traced window: the mean least time of the calls made there
    (``costs``) over the mean device time of the program's runs."""
    tw = ctx.get("trace_window")
    calls = (ctx.get("kernel_calls") or {}).get(kernel)
    if tw is None or not calls:
        return None
    prefix = (ctx["config"].get("kernels") or {}).get(kernel)
    if not prefix:
        return None
    runs, dev_s = xplane.module_time(tw.trace, prefix, tw.start, tw.end)
    if not runs:
        return None
    peaks = costs.peaks_for(ctx["device_kind"])
    least = stats.mean([costs.floor_seconds(ops, nbytes, peaks)[0]
                        for ops, nbytes in calls])
    return 100.0 * least / (dev_s / runs)
