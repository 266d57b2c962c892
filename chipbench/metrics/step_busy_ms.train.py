"""step_busy_ms.train: device-busy time per run of the compiled train step in
the traced window (union of op intervals inside each run, averaged over
chips)."""
from chipbench.trace import module_stats


def read(ctx):
    runs, busy, _ = module_stats(ctx["trace"], r"train_step")
    return busy / runs * 1e3 if runs else None
