"""Longest stretch of the window in which nothing was answered, ms."""


def read(ctx):
    t = ctx.get("answer_times")
    if not t or len(t) < 2:
        return None
    return 1e3 * max(b - a for a, b in zip(t, t[1:]))
