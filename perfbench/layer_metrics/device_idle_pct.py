"""Share of the traced window in which no operation ran on the device, %."""
from perfbench import xplane


def read(ctx):
    tw = ctx.get("trace_window")
    if tw is None or tw.end <= tw.start:
        return None
    busy = xplane.busy_seconds(tw.trace, tw.start, tw.end)
    return 100.0 * (1.0 - busy / (tw.end - tw.start))
