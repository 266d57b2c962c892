"""Pure-jnp oracles for every Pallas kernel and XLA form (the allclose
ground truth).

``rmsnorm`` is also the XLA execution path of ``ops.fused_rmsnorm``.
``ssd_scan`` is the sequential SSD recurrence: the oracle of both the Pallas
kernel and of the chunked forms in ``ssd_xla`` (``ops.ssd(impl="xla")`` and
the prefill of ``ops.ssd_with_state``), and the decode step's path there.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def attention(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, T, D)
    v: jax.Array,  # (B, Hkv, T, D)
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    logits_soft_cap: float = 0.0,
) -> jax.Array:
    """Reference GQA attention. Returns (B, Hq, S, D)."""
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(D).astype(jnp.float32)

    qf = q.astype(jnp.float32).reshape(B, Hkv, group, S, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bhgsd,bhtd->bhgst", qf, kf) * scale
    if logits_soft_cap > 0:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    if causal:
        # queries are the last S positions of the T-long key sequence
        q_pos = jnp.arange(S) + (T - S)
        mask = q_pos[:, None] >= jnp.arange(T)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", probs, vf)
    return out.reshape(B, Hq, S, D).astype(q.dtype)


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Reference RMSNorm over the last dim."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)


def ssd_scan(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)   (already softplus'd, positive)
    A: jax.Array,  # (H,)        (negative)
    Bm: jax.Array,  # (B, S, N)
    C: jax.Array,  # (B, S, N)
    D: Optional[jax.Array] = None,  # (H,)
    *,
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
    return_state: bool = False,
):
    """Reference Mamba-2 SSD recurrence (sequential scan over time).

    h_t = exp(A * dt_t) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t . C_t + D * x_t
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bf, Cf = Bm.astype(jnp.float32), C.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    h0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((Bsz, H, P, N), jnp.float32)
    )

    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs  # (B,H,P), (B,H), (B,N), (B,N)
        decay = jnp.exp(Af[None, :] * dt_t)  # (B,H)
        dx = dt_t[..., None] * x_t  # (B,H,P)
        h = h * decay[..., None, None] + dx[..., None] * b_t[:, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", h, c_t)
        return h, y

    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(Bf, 1, 0),
        jnp.moveaxis(Cf, 1, 0),
    )
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S, H, P)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] * xf
    y = y.astype(x.dtype)
    if return_state:
        return y, h_final
    return y
