"""Rows that stood free with the engine's queue empty (whatever waits is
upstream of it, or the cell is dry), % of the row-steps (`max_batch` a
decode launch) inside the window."""
from perfbench.layer_metrics._common import ratio


def read(ctx):
    share = ratio(ctx, "row_steps_unfed", "row_steps_total")
    return None if share is None else 100.0 * share
