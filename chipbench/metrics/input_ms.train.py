"""input_ms.train: host time to make and place one batch (the benchmark's
``input`` span around ``SyntheticPipeline.next_batch`` and ``device_put``),
mean over the untraced window."""


def read(ctx):
    w = ctx["window"]
    d = ctx["spans"].durations("input", w["t0"], w["t1"])
    return sum(d) / len(d) * 1e3 if d else None
