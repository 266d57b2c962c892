"""Break one traced window of a cell down by the names the program gives
its work, and print it as one JSON line.

    python3 chipbench/breakdown.py --workload yi-6b.train.s4k --seed 7

The cell is set up as ``run.py`` sets it up (same configuration, traffic and
seed), warmed, and one window is traced: the mix's ``trace_steps`` train
steps, or one serve batch. The line holds what ``trace.reduce`` gives, and
from ``scopes.py``: the train step's device time per step by layer scope
beside its busy time per step; the program's host spans; the longest idle
gaps named by them, and the idle time under each; and for serve the
device-idle time between consecutive runs of the decode step beside the
batch's median gap between tokens.
Like ``run.py`` it refuses to run off a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), os.path.dirname(HERE)]

from chipbench import run  # noqa: E402


def _traced(fn):
    """``fn``'s result and the events of its profiler trace."""
    import jax

    from chipbench import trace as tr

    os.makedirs(tr.ARTIFACTS, exist_ok=True)
    log_dir = tempfile.mkdtemp(prefix="chipbench_trace.", dir=tr.ARTIFACTS)
    try:
        jax.profiler.start_trace(log_dir)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        return out, tr.load(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def _ms(seconds: dict) -> dict:
    return {k: v * 1e3 for k, v in seconds.items()}


def _spans_ms(spans: dict) -> dict:
    return {k: {"n": len(v), "mean_ms": statistics.mean(v) * 1e3, "total_ms": sum(v) * 1e3}
            for k, v in spans.items()}


def breakdown(workload: str, seed: int, *, rehearse: bool = False) -> dict:
    from chipbench import scopes as sc
    from chipbench import trace as tr

    cell, files = run.make_cell(workload, seed, 0.0, True, rehearse=rehearse)
    mode = files["traffic"]["mode"]
    out = {"workload": workload, "seed": seed, "device": cell.devices[0].device_kind}
    if mode == "train":
        from chipbench.modes.train import SPANS, Trainer

        trainer = Trainer(cell)
        trainer.window(steps=2)
        win, events = _traced(lambda: trainer.window(steps=cell.traffic["trace_steps"]))
        paths = sc.op_paths(trainer.compiled.as_text())
    else:
        from chipbench.modes.serve import SPANS, Server, drain

        server = Server(cell)
        drain(cell.devices)  # compiles the window's closing program
        win, events = _traced(lambda: server.window(batches=1))
        paths = {}
    red = tr.reduce(events, SPANS)
    lo, hi = tr.window(events)
    out.update(window_ms=red["window_s"] * 1e3, busy_ms=red["busy_s"] * 1e3,
               program_spans=_spans_ms(sc.program_spans(events, lo, hi)),
               idle_gaps_program=sc.idle_gaps_program(events, lo, hi),
               idle_under_ms=_ms(sc.idle_under(events, lo, hi)),
               idle_gaps=red["idle_gaps"])
    if mode == "train":
        runs, busy, _ = tr.module_stats(red, "train_step")
        steps = runs or win["steps"]
        per_step = {k: v / steps for k, v in sc.scopes(events, paths, lo, hi).items()}
        out.update(steps=steps, step_busy_ms=busy / steps * 1e3 if runs else None,
                   scopes_ms_per_step=_ms(per_step),
                   scopes_sum_ms_per_step=sum(per_step.values()) * 1e3)
    else:
        decode = tr.runs_by_dispatch(red, "decode", ("prefill", "decode"))
        # both of the engine's programs carry one name: the first gap is the
        # one after the prefill
        gaps = sc.run_gaps(events, r"^jit__unknown", lo, hi)[1:]
        out.update(decode_busy_ms=(sum(b for b, _ in decode) / len(decode) * 1e3
                                   if decode else None),
                   decode_gap_ms=statistics.mean(gaps) * 1e3 if gaps else None,
                   token_gap_median_ms=statistics.median(win["gaps"]) * 1e3)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    try:
        out = breakdown(args.workload, args.seed)
    except run.CellError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
