"""Rows held by admitted requests whose prompt was still being prefilled,
% of the row-steps (`max_batch` a decode launch) inside the window."""
from perfbench.layer_metrics._common import ratio


def read(ctx):
    share = ratio(ctx, "row_steps_prefilling", "row_steps_total")
    return None if share is None else 100.0 * share
