"""Readings that the limits of ``correct`` are set from, in one process:

* the program's numbers on each of ``--seeds`` (as a run's check reads them);
* the control's on each of ``--control-seeds``: the plain reference put in
  the program's place with every matrix product's operands rounded to fp8
  (the precision below the configuration's bfloat16), compared with the
  float32 reference as the program is;
* the faults a cell can have, on the control seeds: for training, half of
  each batch's tokens left out (every row's first half kept, the mean taken
  over it), planted in the reference put in the program's place (a step
  that returns its state unchanged reads 1 on ``grad_gap`` and
  ``change_gap`` by construction); for serving, one served token altered
  (the next vocabulary id), read from the same reference logits.

    python3 chipbench/control.py --workload W --seeds 1 2 ... --control-seeds 7 8 9 \\
        --out chiprun_out/control/W.json

Every control and fault reading is judged by the cell's limits; the script
exits 1 where one of them passes, and names it. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), os.path.dirname(HERE)]

import numpy as np  # noqa: E402

from chipbench.run import make_cell  # noqa: E402


def half_tokens(batches: list) -> list:
    """Each row's first half: half of the batch's tokens left out."""
    return [b[:, : b.shape[1] // 2] for b in batches]


def _worst(prog: dict, ref: dict, keys=None) -> str:
    """The slice that sets ``check.worst_slice`` (for the log)."""
    keys = list(keys if keys is not None else ref)
    med = statistics.median(ref[k] for k in keys)
    k = max(keys, key=lambda k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30))
    return f"{k[0]}[{k[1]}]"


def _train_numbers(prog: dict, ref: dict) -> dict:
    from chipbench.check import moved_slices, train_numbers

    return dict(train_numbers(prog, ref),
                grad_worst=_worst(prog["grad_norms"], ref["grad_norms"]),
                change_worst=_worst(prog["change_norms"], ref["change_norms"],
                                    moved_slices(ref["grad_norms"])))


def train_readings(cell, seeds, control_seeds) -> dict:
    from chipbench.modes.train import Trainer, reference_numbers
    from chipbench.reference.common import Products

    out = {"program": {}, "control": {}, "half_batch": {}}
    trainer = Trainer(cell)
    out["plan"], out["step_memory"] = trainer.plan_text, trainer.memory
    for seed in dict.fromkeys(list(seeds) + list(control_seeds)):
        trainer.reset(seed)
        readings = trainer.checked_steps(cell.traffic["checked_steps"])
        readings["seed"] = seed
        trainer.params = trainer.opt = None
        t = time.perf_counter()
        ref = reference_numbers(cell, readings, Products())
        ref_s = time.perf_counter() - t
        if seed in seeds:
            out["program"][seed] = dict(_train_numbers(readings, ref), reference_s=ref_s)
        if seed in control_seeds:
            ctl = reference_numbers(cell, readings, Products(fp8=True))
            out["control"][seed] = _train_numbers(ctl, ref)
            half = reference_numbers(cell, dict(readings, tokens=half_tokens(readings["tokens"])),
                                     Products())
            out["half_batch"][seed] = _train_numbers(half, ref)
        _log(seed, out)
    return out


def serve_readings(cell, seeds, control_seeds) -> dict:
    from chipbench.modes.serve import Server, reference_gaps
    from chipbench.reference.common import Products, served_gaps

    out = {"program": {}, "control": {}, "token": {}}
    server = Server(cell)
    dtype = server.engine.cfg.dtype
    k = cell.traffic["checked_requests"]
    for seed in dict.fromkeys(list(seeds) + list(control_seeds)):
        server.reset(seed)
        win = server.window(batches=1)
        sample = server.check_sample(win, k)
        server.engine.params = None
        t = time.perf_counter()
        ref = reference_gaps(cell, sample, Products(), dtype)
        ref_s = time.perf_counter() - t
        gaps = served_gaps(ref, sample["served"], sample["lens"])
        if seed in seeds:
            out["program"][seed] = {"logit_gap": float(gaps.max()), "reference_s": ref_s}
        if seed in control_seeds:
            ctl = np.asarray(reference_gaps(cell, sample, Products(fp8=True), dtype))
            picks = ctl.argmax(axis=-1)
            out["control"][seed] = {
                "logit_gap": float(served_gaps(ref, picks, sample["lens"]).max())}
            altered = sample["served"].copy()
            altered[0, 0] = (altered[0, 0] + 1) % cell.arch.vocab
            out["token"][seed] = {
                "logit_gap": float(served_gaps(ref, altered, sample["lens"]).max())}
        _log(seed, out)
    return out


def _verdicts(cell, out: dict) -> list:
    """Each control and fault reading judged by the cell's limits, in place;
    returns those that pass, which a sound limit never lets happen."""
    from chipbench.check import judge

    passed = []
    for kind in ("control", "half_batch", "token"):
        for seed, v in out.get(kind, {}).items():
            v["correct"] = judge(v, cell.limits)[0]
            if v["correct"]:
                passed.append((kind, seed))
    return passed


def _log(seed, out):
    print(json.dumps({"seed": seed, **{k: v.get(seed) for k, v in out.items()
                                       if isinstance(v, dict)}}),
          file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell, files = make_cell(args.workload, args.seeds[0], 0.0, False)
    mode = files["traffic"]["mode"]
    fn = train_readings if mode == "train" else serve_readings
    res = fn(cell, args.seeds, args.control_seeds)
    passed = _verdicts(cell, res)
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
