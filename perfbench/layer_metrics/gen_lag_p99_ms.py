"""How late the load generator sent, 99th percentile, ms."""
from perfbench import stats


def read(ctx):
    lag = ctx.get("gen_lag_s")
    return 1e3 * stats.percentile(lag, 99) if lag else None
