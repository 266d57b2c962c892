"""search_ms: host time of the mode-1 search (``pick_strategy``) in set-up."""


def read(ctx):
    d = ctx["spans"].durations("search")
    return d[0] * 1e3 if d else None
