"""Rows that stood free because the head of the queue was short of KV
blocks or of a state slot, % of the row-steps (`max_batch` a decode
launch) inside the window."""
from perfbench.layer_metrics._common import ratio


def read(ctx):
    blocks = ratio(ctx, "row_steps_blocked", "row_steps_total")
    state = ratio(ctx, "row_steps_blocked_state", "row_steps_total")
    if blocks is None or state is None:
        return None
    return 100.0 * (blocks + state)
