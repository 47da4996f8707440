"""Share (%) of the traced decode steps' least bytes that is the
sequences' state, read and written: what a carried state costs a step
beside the weights and the selected context."""


def read(ctx):
    state = ctx.get("decode_state_bytes")
    calls = (ctx.get("kernel_calls") or {}).get("decode_step")
    if not state or not calls or len(state) != len(calls):
        return None
    total = sum(nbytes for _, nbytes in calls)
    return 100.0 * sum(state) / total if total else None
