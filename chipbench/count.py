"""Required work of one step, computed from shapes alone.

"Required" is the work the algorithm cannot do without: every matrix
product of the forward and backward passes counted once (recompute left
out), causal attention with its masked half left out, the embedding
lookup left out. So no implementation reads above 100 % of a peak, and a
later kernel is held to the same work as today's XLA path.

Each layer's part comes from its family's reference module
(``chipbench/reference/__init__.py`` gives the contract), summed over the
layers in the family's pattern; the head and the arithmetic every family
shares are here. Shapes come from a configuration file's ``arch`` object
(the keys of the program's ``ModelArch``), so this module imports nothing
of the program.
"""
from __future__ import annotations

import json
import math
import os

from chipbench.reference import family
from chipbench.reference.common import param_shapes

PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_PATH) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _layers(a: dict):
    """The family's module and the kind of each layer."""
    fam = family(a["family"])
    return fam, fam.pattern(a)


def matmul_params(a: dict) -> int:
    """Weights of all layers that enter a matrix product."""
    fam, pattern = _layers(a)
    return sum(fam.matmul_params(kind, a) for kind in pattern)


def head_params(a: dict) -> int:
    return a["hidden"] * a["vocab"]


def mixer_flops_per_token(a: dict, keys) -> float:
    """Forward FLOPs per token of every layer's sequence mixer over ``keys``
    earlier positions."""
    fam, pattern = _layers(a)
    return sum(fam.mixer_flops(kind, a, keys) for kind in pattern)


def _forward_flops_per_token(a: dict, seq: int) -> float:
    """Every matmul, and the mixers over the causal mask's seq / 2 keys on
    average."""
    return 2.0 * (matmul_params(a) + head_params(a)) + mixer_flops_per_token(a, seq / 2)


def train_flops_per_token(a: dict, seq: int) -> float:
    """Forward plus backward (3 x forward) of every matmul and mixer."""
    return 3.0 * _forward_flops_per_token(a, seq)


def train_flops_per_step(a: dict, batch: int, seq: int) -> float:
    return train_flops_per_token(a, seq) * batch * seq


def decode_step_work(a: dict, batch: int, position: int,
                     weight_bytes: int = 2, kv_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one decode step of ``batch`` sequences needs when
    each holds ``position`` earlier tokens: every weight read once (the
    embedding only for the rows looked up), the KV cache of those positions
    read once and one new entry written, and each layer's recurrent state
    read and written (``kv_bytes`` is the cache's element size)."""
    fam, pattern = _layers(a)
    flops = 2.0 * batch * (matmul_params(a) + head_params(a))
    flops += batch * mixer_flops_per_token(a, position + 1)
    shapes = param_shapes(fam, a)
    leaves = sum(math.prod(s) for k, s in shapes.items() if k not in ("embed", "lm_head"))
    weights = (leaves + head_params(a) + batch * a["hidden"]) * weight_bytes
    kv = sum(batch * fam.kv_entries(kind, a) * (position + 1) * kv_bytes for kind in pattern)
    state = sum(fam.decode_state_bytes(kind, a, batch, kv_bytes) for kind in pattern)
    return flops, float(weights + kv + state)


def prefill_flops(a: dict, batch: int, prompt: int) -> float:
    """Forward FLOPs of one batch prefill (causal, masked half left out)."""
    return batch * prompt * _forward_flops_per_token(a, prompt)
