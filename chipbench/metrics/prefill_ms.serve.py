"""prefill_ms.serve: device-busy time per run of the compiled batch prefill in the
traced batch. The engine jits its prefill and decode step as
``functools.partial`` objects, which the trace names alike, so runs are
told apart by the order of the benchmark's dispatch spans."""
from chipbench.trace import runs_by_dispatch


def read(ctx):
    runs = runs_by_dispatch(ctx["trace"], "prefill", ("prefill", "decode"))
    return sum(b for b, _ in runs) / len(runs) * 1e3 if runs else None
