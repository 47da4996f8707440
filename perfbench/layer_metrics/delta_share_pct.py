"""Share of the traced calls' least time that their delta-rule layers'
own part would take, %: over the decode steps and the chunks of the traced
window, 100 x the sum of the floors of each call's rule, convolutions,
states and tails alone (``costs_delta_moe.decode_delta`` / ``chunk_delta``)
over the sum of the whole calls' floors.  A floor is the larger of
operations over the chip's peak and bytes over its bandwidth
(``costs.floor_seconds``); off a chip whose peaks the benchmark knows, or
of a program without such layers, there is nothing to read."""
from perfbench import costs


def read(ctx):
    whole, part = ctx.get("kernel_calls") or {}, ctx.get("delta_calls") or {}
    try:
        peaks = costs.peaks_for(ctx["device_kind"])
    except KeyError:
        return None
    total = delta = 0.0
    for kind, calls in part.items():
        if len(calls) != len(whole.get(kind, ())):
            return None
        delta += sum(costs.floor_seconds(o, b, peaks)[0] for o, b in calls)
        total += sum(costs.floor_seconds(o, b, peaks)[0]
                     for o, b in whole[kind])
    return 100.0 * delta / total if total else None
