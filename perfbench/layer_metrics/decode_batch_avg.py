"""Rows a decode step carried inside the window (decode tokens a step)."""
from perfbench.layer_metrics._common import ratio


def read(ctx):
    return ratio(ctx, "decode_tokens", "decode_steps")
