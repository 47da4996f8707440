"""State-pool bytes held by admitted sequences when the window closed,
GB: state slots in use x bytes of a slot, both as the program counts
them."""


def read(ctx):
    end = (ctx.get("counters") or {}).get("end") or {}
    if "state_slots_used" not in end or "state_slot_bytes" not in ctx:
        return None
    return end["state_slots_used"] * ctx["state_slot_bytes"] / 1e9
