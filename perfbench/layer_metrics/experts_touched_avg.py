"""Distinct experts that got a token, a layer a decode step, inside the
window: it sets the step's weight floor."""
from perfbench.layer_metrics._common import ratio


def read(ctx):
    return ratio(ctx, "experts_touched_sum", "expert_steps_layers")
