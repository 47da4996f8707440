"""Roofline share of the whole-prompt prefill program (compute bound), %."""
from perfbench.layer_metrics._common import roofline


def read(ctx):
    return roofline(ctx, "prefill")
