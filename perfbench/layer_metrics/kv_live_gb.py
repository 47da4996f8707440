"""KV pool bytes held by admitted requests when the window closed, GB."""


def read(ctx):
    end = (ctx.get("counters") or {}).get("end") or {}
    if "kv_blocks_used" not in end or "kv_block_bytes" not in ctx:
        return None
    return end["kv_blocks_used"] * ctx["kv_block_bytes"] / 1e9
