"""Programs compiled inside the measured window; should read 0."""
from perfbench.layer_metrics._common import delta


def read(ctx):
    return delta(ctx, "compile_count")
