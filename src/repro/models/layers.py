"""Shared building blocks: RoPE, norms, GQA attention (+KV cache), SwiGLU.

Everything is a pure function over explicit param dicts; layer params are
stacked on a leading L axis and consumed by ``lax.scan`` in lm.py.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding. x: (B, H, S, D), positions: (B, S) or (S,)."""
    D = x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,S,half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def norm(x: jax.Array, w: jax.Array, impl: str = "xla", eps: float = 1e-6) -> jax.Array:
    return ops.fused_rmsnorm(x, w, eps=eps, impl=impl)


def _sliding_attention(q, k, v, window: int) -> jax.Array:
    """Reference banded attention (XLA path only)."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, group, S, D)
    logits = jnp.einsum("bhgsd,bhtd->bhgst", qf, k.astype(jnp.float32))
    logits /= jnp.sqrt(D).astype(jnp.float32)
    pos = jnp.arange(S)
    mask = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < window)
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, S, D).astype(q.dtype)


def cross_attention_block(
    p: dict,
    x: jax.Array,  # (B, S, d) decoder stream
    enc_kv: tuple[jax.Array, jax.Array],  # precomputed (B, Hkv, T_enc, D) x2
    arch,
    *,
    attn_impl: str = "pallas",
) -> jax.Array:
    B, S, _ = x.shape
    H, D = arch.heads, arch.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, D).transpose(0, 2, 1, 3)
    k, v = enc_kv
    out = ops.flash_attention(q, k, v, causal=False, impl=attn_impl)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * D)
    return out @ p["wo"]


def swiglu(p: dict, x: jax.Array, constrain=None) -> jax.Array:
    """Gated MLP: wi packs [gate; up] on the output dim.

    ``constrain(x, dims)`` (optional, ModelCfg.constrain) pins the FFN
    intermediate's sharding — GSPMD's propagation loses it through the
    remat'd backward otherwise (§Perf H2b)."""
    gate_up = x @ p["wi"]  # (B, S, 2F)
    if constrain is not None:
        gate_up = constrain(gate_up, ("b", None, "m"))
    gate, up = jnp.split(gate_up, 2, axis=-1)
    hidden = jax.nn.silu(gate) * up
    if constrain is not None:
        hidden = constrain(hidden, ("b", None, "m"))
    return hidden @ p["wo"]
