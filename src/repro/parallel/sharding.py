"""Sharding rules: Astra strategy -> PartitionSpecs for params/batch/caches.

The production mesh is ("data", "model") or ("pod", "data", "model")
(launch/mesh.py). An Astra :class:`ParallelStrategy` maps onto it as:

    data parallel        -> ("pod", "data") on the batch dim
    tensor parallel      -> "model" on heads / ffn / vocab dims
    distributed optimizer / FSDP (ZeRO-3) -> "model"-orthogonal dim of each
        large weight additionally sharded over "data"
    expert parallel      -> expert dim over "data" when divisible
    sequence parallel    -> seq dim of activations over "model"
        (applied via sharding constraints in train_step)

Every rule degrades gracefully: a dim that is not divisible by its mesh axis
stays unsharded (recorded in the plan for the roofline notes).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.arch import ModelArch


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """Resolved axis names + toggles for one (mesh, strategy) pair."""

    mesh: Mesh
    batch_axes: tuple[str, ...]  # axes sharding the batch dim
    model_axis: Optional[str]  # tensor-parallel axis
    fsdp: bool  # shard weights/opt-state over the data axis too
    sequence_parallel: bool = False

    @property
    def data_axis(self) -> Optional[str]:
        return "data" if "data" in self.mesh.axis_names else None

    def axis_size(self, name: Optional[str]) -> int:
        if name is None:
            return 1
        return self.mesh.shape[name]

    def batch_size_divisor(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]) or 1)


def constrain_batch_sharding(x, batch_axes: tuple[str, ...] = ("pod", "data")):
    """Pin dim 0 of ``x`` to the batch axes of the mesh set by
    ``jax.set_mesh`` (a no-op outside one).

    Layer-scan carries must not be left to GSPMD propagation: with weights
    sharded over both "model" and "data" (FSDP x TP) the partitioner picks a
    batch-dim resharding for the carry that forces involuntary
    rematerializations and has miscompiled the scan outright (dp-parity
    divergence of O(0.1) in the loss). An explicit constraint keeps the
    carry data-sharded, which is the correct layout.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    if not axes:
        return x
    size = int(np.prod([mesh.shape[a] for a in axes]))
    if size <= 1 or x.shape[0] % size != 0:
        return x
    return jax.lax.with_sharding_constraint(x, P(axes, *([None] * (x.ndim - 1))))


def make_plan(
    mesh: Mesh,
    *,
    fsdp: bool = True,
    sequence_parallel: bool = False,
) -> ShardingPlan:
    axes = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in axes)
    model_axis = "model" if "model" in axes else None
    return ShardingPlan(
        mesh=mesh,
        batch_axes=batch_axes,
        model_axis=model_axis,
        fsdp=fsdp and "data" in axes,
        sequence_parallel=sequence_parallel,
    )


def _div(dim: int, plan: ShardingPlan, axis: Optional[str]) -> bool:
    return axis is not None and dim % plan.axis_size(axis) == 0


def _spec2(plan: ShardingPlan, shape: tuple[int, ...], tp_dim: int,
           fsdp_dim: Optional[int]) -> P:
    """Shard tp_dim over "model"; optionally fsdp_dim over "data"."""
    parts: list[Any] = [None] * len(shape)
    if _div(shape[tp_dim], plan, plan.model_axis):
        parts[tp_dim] = plan.model_axis
    if (
        plan.fsdp
        and fsdp_dim is not None
        and fsdp_dim != tp_dim
        and _div(shape[fsdp_dim], plan, plan.data_axis)
    ):
        parts[fsdp_dim] = plan.data_axis
    return P(*parts)


def param_specs(arch: ModelArch, plan: ShardingPlan, params_shape: dict) -> dict:
    """PartitionSpec pytree matching ``init_params`` structure.

    ``params_shape`` is the eval_shape pytree (shapes are needed to check
    divisibility without materializing anything).
    """

    def leaf_spec(path: tuple[str, ...], shape: tuple[int, ...]) -> P:
        name = ".".join(path)
        last = path[-1]
        # --- embeddings / head -----------------------------------------
        if name == "embed":
            return _spec2(plan, shape, tp_dim=0, fsdp_dim=1)  # vocab x d
        if name == "lm_head":
            return _spec2(plan, shape, tp_dim=1, fsdp_dim=0)  # d x vocab
        if "norm" in last or last.startswith("ln"):
            return P(*([None] * len(shape)))
        # --- stacked layer tensors (leading L axis) ---------------------
        if last == "wqkv" or last == "wq" or last == "wkv":
            return _spec2(plan, shape, tp_dim=len(shape) - 1, fsdp_dim=len(shape) - 2)
        if last == "wo":
            return _spec2(plan, shape, tp_dim=len(shape) - 2, fsdp_dim=len(shape) - 1)
        if last == "wi":  # (L, d, 2F) or (L, E, d, 2F)
            if len(shape) == 4:  # MoE experts
                parts: list[Any] = [None, None, None, None]
                if _div(shape[1], plan, plan.data_axis) and plan.fsdp:
                    parts[1] = plan.data_axis  # expert parallelism
                if _div(shape[3], plan, plan.model_axis):
                    parts[3] = plan.model_axis
                return P(*parts)
            return _spec2(plan, shape, tp_dim=len(shape) - 1, fsdp_dim=len(shape) - 2)
        if last == "router":
            return P(*([None] * len(shape)))
        if last in ("in_proj",):
            return _spec2(plan, shape, tp_dim=len(shape) - 1, fsdp_dim=len(shape) - 2)
        if last in ("out_proj",):
            return _spec2(plan, shape, tp_dim=len(shape) - 2, fsdp_dim=len(shape) - 1)
        if last in ("conv_w", "conv_b"):
            return _spec2(plan, shape, tp_dim=len(shape) - 1, fsdp_dim=None)
        if last in ("dt_bias", "A_log", "D"):
            return _spec2(plan, shape, tp_dim=len(shape) - 1, fsdp_dim=None)
        if last == "wo" :
            return _spec2(plan, shape, tp_dim=len(shape) - 2, fsdp_dim=len(shape) - 1)
        # moe.wo (L, E, F, d)
        if len(shape) == 4:
            parts = [None, None, None, None]
            if _div(shape[1], plan, plan.data_axis) and plan.fsdp:
                parts[1] = plan.data_axis
            if _div(shape[2], plan, plan.model_axis):
                parts[2] = plan.model_axis
            return P(*parts)
        return P(*([None] * len(shape)))

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        return leaf_spec(path, tuple(node.shape))

    specs = walk(params_shape, ())
    # moe.wo needs its own rule (leaf name "wo" collides with attn.wo)
    def fix_moe(node, path):
        if isinstance(node, dict):
            return {k: fix_moe(v, path + (k,)) for k, v in node.items()}
        if len(path) >= 2 and path[-2] == "moe" and path[-1] == "wo":
            shape = _lookup(params_shape, path).shape  # (L, E, F, d)
            parts: list[Any] = [None] * len(shape)
            if plan.fsdp and _div(shape[1], plan, plan.data_axis):
                parts[1] = plan.data_axis
            if _div(shape[2], plan, plan.model_axis):
                parts[2] = plan.model_axis
            return P(*parts)
        return node

    return fix_moe(specs, ())


def _lookup(tree: dict, path: tuple[str, ...]):
    node = tree
    for k in path:
        node = node[k]
    return node


def batch_spec(plan: ShardingPlan, batch_shape: dict) -> dict:
    """Specs for the input batch: batch dim over ("pod","data")."""

    def leaf(name, x):
        nd = len(x.shape)
        bs = x.shape[0]
        if bs % plan.batch_size_divisor() == 0 and plan.batch_axes:
            return P(plan.batch_axes, *([None] * (nd - 1)))
        return P(*([None] * nd))

    return {k: leaf(k, v) for k, v in batch_shape.items()}


def cache_specs(arch: ModelArch, plan: ShardingPlan, cache_shape: dict) -> dict:
    """Decode-cache specs: batch over data axes; heads (or seq) over model."""
    out = {}
    for name, x in cache_shape.items():
        shape = x.shape
        parts: list[Any] = [None] * len(shape)
        # all caches are (L, B, ...): shard B over the data axes
        if len(shape) >= 2 and shape[1] % plan.batch_size_divisor() == 0 and plan.batch_axes:
            parts[1] = plan.batch_axes
        if name in ("k", "v", "enc_k", "enc_v"):
            # (L, B, Hkv, T, D): heads over model when divisible, else seq
            if _div(shape[2], plan, plan.model_axis):
                parts[2] = plan.model_axis
            elif _div(shape[3], plan, plan.model_axis):
                parts[3] = plan.model_axis
        elif name in ("k_scale", "v_scale"):
            # (L, B, Hkv, T): mirror the k/v layout minus the head_dim axis
            if _div(shape[2], plan, plan.model_axis):
                parts[2] = plan.model_axis
            elif _div(shape[3], plan, plan.model_axis):
                parts[3] = plan.model_axis
        elif name == "state":
            # (L, B, H, P, N): ssm heads over model
            if _div(shape[2], plan, plan.model_axis):
                parts[2] = plan.model_axis
        elif name == "conv":
            # (L, B, K-1, conv_dim): channels over model
            if _div(shape[3], plan, plan.model_axis):
                parts[3] = plan.model_axis
        out[name] = P(*parts)
    return out


def named(plan: ShardingPlan, spec_tree, target_tree=None):
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(plan.mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
