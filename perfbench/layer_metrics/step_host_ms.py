"""Host time of a tensor_llm step: the step less its device waits, ms."""
from perfbench.layer_metrics._spans import steps


def read(ctx):
    found = steps(ctx)
    if not found:
        return None
    host = sum(s["timer"] - s.get("wait", 0.0) for s in found)
    return 1e3 * host / len(found)
