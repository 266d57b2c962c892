"""Mamba-2 mixer block: projections + depthwise conv + SSD scan.

Single-group (G=1) SSD as in the Mamba-2 370m config: per-head scalar decay
A, shared B/C streams of width ssm_state, headdim = d_inner / nheads; with
``arch.ssm_gated_norm``, the gated RMSNorm rmsnorm(y * silu(z)) (gain
``ln1``) before the out projection.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops

CONV_K = 4


class SSMCache(NamedTuple):
    """Per-layer-stacked decode state."""

    conv: jax.Array  # (L, B, CONV_K - 1, conv_dim) last inputs
    state: jax.Array  # (L, B, H, P, N)


def _depthwise_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Causal depthwise conv along seq. x: (B, S, C), w: (K, C)."""
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + x.shape[1], :] * w[i] for i in range(K))
    return out


def ssm_block(
    p: dict,
    x: jax.Array,  # (B, S, d)
    arch,
    *,
    ssm_impl: str = "xla",
    cache: Optional[tuple[jax.Array, jax.Array]] = None,  # (conv (B,K-1,C), state (B,H,P,N))
) -> tuple[jax.Array, Optional[tuple[jax.Array, jax.Array]]]:
    B, S, d = x.shape
    d_inner = arch.ssm_expand * arch.hidden
    H = arch.ssm_heads or max(d_inner // 64, 1)
    P = d_inner // H
    N = arch.ssm_state

    zxbcdt = x @ p["in_proj"]  # (B, S, 2*d_inner + 2N + H)
    z, xbc, dt_raw = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * N], axis=-1)

    new_cache = None
    if cache is None:
        xbc = _depthwise_conv(xbc, p["conv_w"]) + p["conv_b"]
    else:
        conv_cache, state_in = cache
        hist = jnp.concatenate([conv_cache, xbc], axis=1)  # (B, K-1+S, C)
        xbc = _depthwise_conv(hist, p["conv_w"])[:, CONV_K - 1 :] + p["conv_b"]
        new_conv = hist[:, -(CONV_K - 1) :]
    xbc = jax.nn.silu(xbc)
    xs, Bm, C = jnp.split(xbc, [d_inner, d_inner + N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])  # (B, S, H)
    A = -jnp.exp(p["A_log"])  # (H,)

    if cache is None:
        y = ops.ssd(xs, dt, A, Bm, C, p["D"], impl=ssm_impl, chunk=arch.ssm_chunk)
    else:
        y, state_out = ops.ssd_with_state(
            xs, dt, A, Bm, C, p["D"], init_state=state_in, chunk=arch.ssm_chunk
        )
        new_cache = (new_conv, state_out)

    y = y.reshape(B, S, d_inner)
    if arch.ssm_gated_norm:
        # Mamba-2's gated RMSNorm over the whole inner width (one group), in
        # float32 as the published block computes it
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        y = ops.fused_rmsnorm(g, p["ln1"], eps=arch.rms_norm_eps, impl="xla").astype(x.dtype)
    else:
        y = y * jax.nn.silu(z)  # gate
    return y @ p["out_proj"], new_cache
