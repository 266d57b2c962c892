"""pred_err_pct.train: |searched plan's predicted step - measured step| over
the measured step, the measured step being the untraced window's time over
its steps."""


def read(ctx):
    w = ctx["window"]
    measured = w["seconds"] / w["steps"]
    return abs(ctx["predicted_step_s"] - measured) / measured * 100.0
