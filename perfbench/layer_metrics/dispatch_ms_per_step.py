"""Building the host arrays of a step's calls and launching them, ms."""
from perfbench.layer_metrics._spans import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "prep", "dispatch")
