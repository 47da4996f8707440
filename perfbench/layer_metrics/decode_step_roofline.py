"""Roofline share of the decode-step program (memory bound), %."""
from perfbench.layer_metrics._common import roofline


def read(ctx):
    return roofline(ctx, "decode_step")
