"""Share of admissions that found a row free and nothing queued in the
engine (whatever waits is still upstream of it), % of all admissions."""
from perfbench.layer_metrics._spans import count, steps


def read(ctx):
    if not steps(ctx):
        return None
    n = count(ctx, "llm", "admit")
    total = sum(n.values())
    return 100.0 * n.get("admit_none_queued", 0) / total if total else None
