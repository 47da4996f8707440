"""Handing a step's buffers downstream, blocking puts included, ms."""
from perfbench.layer_metrics._spans import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "emit")
