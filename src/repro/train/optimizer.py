"""AdamW with fp32 state, global-norm clipping, cosine schedule.

Implemented from scratch (no optax in this environment). State mirrors the
param pytree, so the ShardingPlan's param specs apply verbatim to mu/nu —
with ``use_distributed_optimizer`` (ZeRO) the FSDP rule already shards the
dominant state dims over "data".
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class OptState(NamedTuple):
    mu: dict
    nu: dict
    step: jax.Array  # int32 scalar


def adamw_init(params) -> OptState:
    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
    return OptState(mu=zeros, nu=jax.tree_util.tree_map(jnp.copy, zeros),
                    step=jnp.zeros((), jnp.int32))


def global_norm(tree) -> jax.Array:
    sq = jax.tree_util.tree_map(
        lambda g: jnp.sum(g.astype(jnp.float32) ** 2), tree
    )
    return jnp.sqrt(jax.tree_util.tree_reduce(jnp.add, sq))


def cosine_schedule(
    base_lr: float, warmup_steps: int, total_steps: int, min_ratio: float = 0.1
):
    def lr(step):
        step = step.astype(jnp.float32)
        warm = base_lr * step / max(warmup_steps, 1)
        frac = jnp.clip(
            (step - warmup_steps) / max(total_steps - warmup_steps, 1), 0.0, 1.0
        )
        cos = base_lr * (min_ratio + (1 - min_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
        return jnp.where(step < warmup_steps, warm, cos)

    return lr


def adamw_update(
    params,
    grads,
    state: OptState,
    *,
    lr,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
    clip_norm: float = 1.0,
):
    """One AdamW step. ``lr`` is a schedule fn or a float. Its work, the
    global norm and the clip included, runs under the ``optimizer`` scope."""
    with jax.named_scope("optimizer"):
        step = state.step + 1
        lr_t = lr(step) if callable(lr) else lr

        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-12))
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32) * scale, grads)

        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mh = m / bc1
            vh = v / bc2
            delta = mh / (jnp.sqrt(vh) + eps)
            # decoupled weight decay on matrices only (norms/biases are 1-D)
            if p.ndim >= 2:
                delta = delta + weight_decay * p.astype(jnp.float32)
            new_p = p.astype(jnp.float32) - lr_t * delta
            return new_p.astype(p.dtype), m, v

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.mu)
        flat_v = treedef.flatten_up_to(state.nu)
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        metrics = {"grad_norm": gnorm, "lr": lr_t}
        return new_p, OptState(mu=new_m, nu=new_v, step=step), metrics
