"""Reading a step's synced logits or tokens back to the host, ms."""
from perfbench.layer_metrics._spans import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "readback")
