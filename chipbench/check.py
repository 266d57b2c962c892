"""The numbers that decide ``correct``, each against its limit.

Training (per cell, over the steps set-up drives before the window):
  loss_gap    largest |loss - reference loss| / |reference loss| of a step
  grad_gap    worst slice (a leaf, or one layer of a stacked leaf): the gap
              between the norms of the program's first raw gradient, worked
              out from its AdamW state after one step, and the reference's,
              over the larger of the reference's norm of that slice and of
              the median slice
  change_gap  the same for the parameters' change after the checked steps,
              leaving out slices whose reference gradient is under a
              thousandth of the median slice's (they move by round-off)
Serving:
  logit_gap   widest gap by which a served greedy token's reference logit
              lies below the reference's best at its position
"""
from __future__ import annotations

import statistics
import sys

from chipbench.reference.common import ADAM

ZERO_GRAD = 1e-3


def slice_gaps(prog: dict, ref: dict, keys=None) -> list:
    """Per slice, the gap between the program's norm and the reference's,
    over the larger of the reference's norm of that slice and of the
    median slice."""
    keys = list(keys if keys is not None else ref)
    med = statistics.median(ref[k] for k in keys)
    return [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys]


def worst_slice(prog: dict, ref: dict, keys=None) -> float:
    return max(slice_gaps(prog, ref, keys))


def raw_grad_norms(mu_norms: dict, grad_norm: float) -> dict:
    """The first step's raw gradient per slice from AdamW's first moment
    after one step: mu = (1 - b1) * g * min(1, clip / |g|)."""
    scale = min(1.0, ADAM["clip_norm"] / max(grad_norm, 1e-12))
    return {k: v / ((1 - ADAM["b1"]) * scale) for k, v in mu_norms.items()}


def moved_slices(ref_grad: dict) -> list:
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= ZERO_GRAD * med]


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog``: losses, grad_norms (raw), change_norms; ``ref`` likewise."""
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"]))
    grad = slice_gaps(prog["grad_norms"], ref["grad_norms"])
    change = slice_gaps(prog["change_norms"], ref["change_norms"],
                        moved_slices(ref["grad_norms"]))
    return {
        "loss_gap": loss,
        "grad_gap": max(grad),
        "change_gap": max(change),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number that has a limit must be finite and within it; a cell's
    limits file leaves out a number that no control or fault separates from
    sound runs, and that number is not compared."""
    out, ok = {}, True
    for name, value in numbers.items():
        if name not in limits:
            continue
        limit = limits[name]
        good = value == value and value <= limit
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    return ok, out


def print_numbers(checked: dict, stream=sys.stderr) -> None:
    for name, v in checked.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}", file=stream)
