"""Readings that the limits of a ``serve_logits`` cell are set from, in one
process, as ``control.py`` reads a ``serve`` cell's:

* the program's ``logit_gap`` and ``logit_rms`` on each of ``--seeds``;
* the control's on each of ``--control-seeds``: the plain reference with
  every matrix product's operands rounded to fp8 put in the program's place
  (its logits, and the greedy tokens they pick);
* two faults on the control seeds: one served token altered (the next
  vocabulary id; ``logit_gap``), and the Mamba layers' recurrent state
  lost, the program's decode step handed zeroed convolution and SSD state
  at every step (``logit_rms``).

    python3 chipbench/control_logits.py --workload W --seeds 1 2 ... \\
        --control-seeds 7 8 9 --out readings/W.json

Every control and fault reading is judged by the cell's limits; the script
exits 1 where one of them passes, and names it. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), os.path.dirname(HERE)]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.run import make_cell  # noqa: E402

FAULTS = ("control", "token", "state")


def state_lost(decode):
    """``decode`` handed zeroed convolution and SSD state at every step."""
    def step(params, caches, **kwargs):
        lost = {k: jnp.zeros_like(caches[k]) for k in ("conv", "state")}
        return decode(params, caches=dict(caches, **lost), **kwargs)
    return step


def readings(cell, seeds, control_seeds) -> dict:
    from chipbench.modes import serve_logits as M
    from chipbench.modes.serve import Server, reference_gaps
    from chipbench.reference.common import Products

    out = {"program": {}, **{f: {} for f in FAULTS}}
    server = Server(cell)
    dtype = server.engine.cfg.dtype
    k = cell.traffic["checked_requests"]
    for seed in dict.fromkeys(list(seeds) + list(control_seeds)):
        server.reset(seed)
        win = server.window(batches=1)
        sample = server.check_sample(win, k)
        logits = M.program_logits(server, win, sample)
        if seed in control_seeds:
            lost = M.program_logits(server, win, sample, state_lost(server.engine._decode))
        server.engine.params = None
        t = time.perf_counter()
        ref = reference_gaps(cell, sample, Products(), dtype)
        ref_s = time.perf_counter() - t
        if seed in seeds:
            out["program"][seed] = dict(M.numbers(ref, sample, logits), reference_s=ref_s)
        if seed in control_seeds:
            ctl = np.asarray(reference_gaps(cell, sample, Products(fp8=True), dtype))
            out["control"][seed] = M.numbers(
                ref, dict(sample, served=ctl.argmax(axis=-1)), ctl)
            altered = sample["served"].copy()
            altered[0, 0] = (altered[0, 0] + 1) % cell.arch.vocab
            out["token"][seed] = {"logit_gap": M.numbers(
                ref, dict(sample, served=altered), logits)["logit_gap"]}
            out["state"][seed] = {"logit_rms": M.logit_rms(lost, ref, sample["lens"])}
        print(json.dumps({"seed": seed, **{f: v.get(seed) for f, v in out.items()}}),
              file=sys.stderr, flush=True)
    return out


def verdicts(cell, out: dict) -> list:
    """Each control and fault reading judged by the cell's limits, in place;
    returns those that pass, which a sound limit never lets happen."""
    from chipbench.check import judge

    passed = []
    for kind in FAULTS:
        for seed, v in out[kind].items():
            v["correct"] = judge(v, cell.limits)[0]
            if v["correct"]:
                passed.append((kind, seed))
    return passed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell, _ = make_cell(args.workload, args.seeds[0], 0.0, False)
    res = readings(cell, args.seeds, args.control_seeds)
    passed = verdicts(cell, res)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, **res}, f, indent=1)
    print(json.dumps({"workload": args.workload, **res}))
    for kind, seed in passed:
        print(f"control: {kind} on seed {seed} passes the limits of {args.workload}",
              file=sys.stderr)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
