"""Bytes of the window layers' pools held by admitted requests when the
window closed, GB: live window blocks x bytes of one, both as the program
counts them (a row holds its newest `sliding_window` positions there,
whatever its context)."""


def read(ctx):
    end = (ctx.get("counters") or {}).get("end") or {}
    if "window_blocks_used" not in end or "window_block_bytes" not in ctx:
        return None
    return end["window_blocks_used"] * ctx["window_block_bytes"] / 1e9
