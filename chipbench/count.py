"""Required work of one step, computed from shapes alone.

"Required" is the work the algorithm cannot do without: every matrix
product of the forward and backward passes counted once (recompute left
out), causal attention with its masked half left out, the embedding
lookup left out. So no implementation reads above 100 % of a peak, and a
later kernel is held to the same work as today's XLA path. The SSD mixer
adds nothing: its sequential form (the program's XLA path) updates its
state elementwise and contracts only the read-out, its chunked form at the
published chunk size computes several times more products, so no count of
its products is a floor for both.

Shapes come from a configuration file's ``arch`` object (the keys of the
program's ``ModelArch``), so this module imports nothing of the program.
"""
from __future__ import annotations

import json
import os

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


def _head_dim(a: dict) -> int:
    return a.get("head_dim") or a["hidden"] // a["heads"]


def layer_matmul_params(a: dict) -> int:
    """Weights of one layer that enter a matrix product."""
    d = a["hidden"]
    if a["family"] == "dense":
        hd = _head_dim(a)
        q, kv = a["heads"] * hd, a["kv_heads"] * hd
        return d * (q + 2 * kv) + q * d + 3 * d * a["ffn"]
    if a["family"] == "ssm":
        di = a.get("ssm_expand", 2) * d
        n, h = a["ssm_state"], a["ssm_heads"]
        return d * (2 * di + 2 * n + h) + di * d
    raise ValueError(f"no count for family {a['family']!r}")


def head_params(a: dict) -> int:
    return a["hidden"] * a["vocab"]


def mixer_flops_per_token(a: dict, seq: int) -> float:
    """Forward FLOPs per token of one layer's sequence mixer: causal
    attention (QK^T and PV over seq/2 keys on average); none for the SSD."""
    if a["family"] == "dense":
        return 2.0 * seq * a["heads"] * _head_dim(a)
    return 0.0


def train_flops_per_token(a: dict, seq: int) -> float:
    """Forward plus backward (3 x forward) of every matmul and mixer."""
    layers = a["num_layers"]
    matmul = 2.0 * (layers * layer_matmul_params(a) + head_params(a))
    return 3.0 * (matmul + layers * mixer_flops_per_token(a, seq))


def train_flops_per_step(a: dict, batch: int, seq: int) -> float:
    return train_flops_per_token(a, seq) * batch * seq


def decode_step_work(a: dict, batch: int, position: int,
                     weight_bytes: int = 2, kv_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one decode step of ``batch`` sequences needs when
    each holds ``position`` earlier tokens: every weight read once (the
    embedding only for the rows looked up), the KV cache of those positions
    read once and one new entry written."""
    if a["family"] != "dense":
        raise ValueError("decode work is counted for attention models only")
    layers, d, hd = a["num_layers"], a["hidden"], _head_dim(a)
    kv_width = 2 * a["kv_heads"] * hd
    flops = 2.0 * batch * (layers * layer_matmul_params(a) + head_params(a))
    flops += 4.0 * batch * layers * (position + 1) * a["heads"] * hd
    norms = (2 * layers + 1) * d
    weights = (layers * layer_matmul_params(a) + head_params(a) + norms
               + batch * d) * weight_bytes
    kv = layers * batch * kv_width * (position + 1) * kv_bytes
    return flops, float(weights + kv)


def prefill_flops(a: dict, batch: int, prompt: int) -> float:
    """Forward FLOPs of one batch prefill (causal, masked half left out)."""
    layers = a["num_layers"]
    matmul = 2.0 * (layers * layer_matmul_params(a) + head_params(a))
    return batch * prompt * (matmul + layers * mixer_flops_per_token(a, prompt))
