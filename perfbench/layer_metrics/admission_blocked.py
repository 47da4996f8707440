"""Times admission found no free KV blocks inside the window."""
from perfbench.layer_metrics._common import delta


def read(ctx):
    return delta(ctx, "admission_blocked")
