"""Share of the device time of all programs in the traced window that the
prefill program took (the configuration's `kernels.prefill`), %."""
from perfbench import xplane


def read(ctx):
    tw = ctx.get("trace_window")
    prefix = ((ctx.get("config") or {}).get("kernels") or {}).get("prefill")
    if tw is None or not prefix:
        return None
    runs, dev_s = xplane.module_time(tw.trace, prefix, tw.start, tw.end)
    _, all_s = xplane.module_time(tw.trace, "", tw.start, tw.end)
    return 100.0 * dev_s / all_s if runs and all_s > 0 else None
