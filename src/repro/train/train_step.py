"""Train-step factory: microbatched grad accumulation + AdamW + sharding.

One Astra strategy maps to one TrainStepCfg (DESIGN.md §5):
  micro_batch_size / num_microbatches -> lax.scan grad accumulation
  recompute_granularity               -> ModelCfg.remat
  use_distributed_optimizer           -> ShardingPlan.fsdp
  bf16 grad accumulation (beyond-paper gradient compression knob)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.arch import ModelArch
from repro.models.lm import ModelCfg, forward_train
from repro.train.optimizer import OptState, adamw_update, cosine_schedule


@dataclasses.dataclass(frozen=True)
class TrainStepCfg:
    num_microbatches: int = 1
    base_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    accum_dtype: Any = jnp.float32  # bf16 => compressed accumulation
    # mesh axes sharding the batch dim: with grad accumulation the reshape
    # (GB, ...) -> (K, GB/K, ...) must keep dim 1 (not the scan dim) sharded,
    # which needs an explicit constraint or GSPMD puts K on the devices.
    batch_axes: tuple = ()


def make_train_step(
    arch: ModelArch,
    model_cfg: ModelCfg,
    cfg: TrainStepCfg,
) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    ``batch["tokens"]``: (global_batch, seq). Grad accumulation splits the
    batch into num_microbatches along dim 0 and scans.
    """
    lr = cosine_schedule(cfg.base_lr, cfg.warmup_steps, cfg.total_steps)

    def loss_fn(params, microbatch):
        return forward_train(params, arch, model_cfg, microbatch)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state: OptState, batch: dict):
        K = cfg.num_microbatches
        if K == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                y = x.reshape((K, x.shape[0] // K) + x.shape[1:])
                if cfg.batch_axes:
                    y = jax.lax.with_sharding_constraint(
                        y, P(None, cfg.batch_axes, *([None] * (y.ndim - 2)))
                    )
                return y

            micro = jax.tree_util.tree_map(split, batch)

            def accum(carry, mb):
                g_acc, l_acc = carry
                (l, _), g = grad_fn(params, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(cfg.accum_dtype), g_acc, g
                )
                return (g_acc, l_acc + l), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, cfg.accum_dtype), params
            )
            (g_sum, l_sum), _ = jax.lax.scan(accum, (g0, 0.0), micro)
            grads = jax.tree_util.tree_map(lambda g: (g / K).astype(jnp.float32), g_sum)
            loss = l_sum / K
            metrics = {"loss": loss}

        params, opt_state, opt_metrics = adamw_update(
            params, grads, opt_state,
            lr=lr, weight_decay=cfg.weight_decay, clip_norm=cfg.clip_norm,
        )
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step
