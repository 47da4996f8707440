"""Share of the window's routed (token, expert) pairs that reached an
expert held on this chip, %: 100 x held / (held + routed away); 100 x
held experts / published experts where routing is even (12.5 for 32 of
256)."""
from perfbench.layer_metrics._common import delta


def read(ctx):
    held = delta(ctx, "expert_pairs_held")
    away = delta(ctx, "expert_pairs_away")
    if held is None or away is None or not held + away:
        return None
    return 100.0 * held / (held + away)
