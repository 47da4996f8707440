"""Tokens at the busiest expert of a prefill chunk (largest over layers)
over the mean load of an expert, clen x experts a token / experts: the
mean over the chunks of the traced stretch whose counts were read back."""
from perfbench import stats


def read(ctx):
    cfg = ctx.get("config") or {}
    spans = ctx.get("chunk_spans") or []
    if not spans or "num_experts" not in cfg:
        return None
    share = float(cfg["num_experts_per_tok"]) / float(cfg["num_experts"])
    return stats.mean([s["expert_load_max"] / (s["clen"] * share)
                       for s in spans])
