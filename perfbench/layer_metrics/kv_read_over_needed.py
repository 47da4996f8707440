"""Pool slots the window's decode steps read over the slots they needed,
in units of a K+V slot: (selected slots gathered + indexer slots read / r)
over (slots attended + slots scored / r), r = bytes of a K+V slot over
bytes of an indexer slot.  At least 1."""
from perfbench.layer_metrics._common import delta


def read(ctx):
    cfg = ctx.get("config") or {}
    got = [delta(ctx, k) for k in ("kv_slots_read", "idx_slots_read",
                                   "kv_tokens_selected", "kv_tokens_scored")]
    if any(g is None for g in got) or "sa_config" not in cfg:
        return None
    r = (2.0 * cfg["num_key_value_heads"] * cfg["head_dim"]
         / cfg["sa_config"]["indexer_head_dim"])
    needed = got[2] + got[3] / r
    return (got[0] + got[1] / r) / needed if needed else None
