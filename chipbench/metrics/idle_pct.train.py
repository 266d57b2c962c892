"""idle_pct.train: 1 - (union of device-op intervals) / traced window."""


def read(ctx):
    red = ctx["trace"]
    return (1.0 - red["busy_s"] / red["window_s"]) * 100.0
