"""Plain float32 decoder with grouped-query attention, RoPE, RMSNorm and a
SwiGLU MLP (the Yi / Llama block), written from the published description.

Where the program departs from the published model, this follows the
program and the configuration file says so: RoPE's base is the
configuration's ``rope_theta`` (the program fixes 10000) and the norms'
epsilon is ``rms_norm_eps``. The weights' layout is the program's (fused
q|k|v projection, fused gate|up projection), read by path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import rmsnorm, silu

ROPE_THETA = 10000.0
NORM_EPS = 1e-6
QUERY_BLOCK = 512


def norm_eps(a: dict) -> float:
    return NORM_EPS


def param_shapes(a: dict) -> dict:
    d, V, L, F = a["hidden"], a["vocab"], a["num_layers"], a["ffn"]
    hd = a.get("head_dim") or d // a["heads"]
    H, Hkv = a["heads"], a["kv_heads"]
    shapes = {
        "embed": (V, d),
        "final_norm": (d,),
        "layers/attn/wqkv": (L, d, (H + 2 * Hkv) * hd),
        "layers/attn/wo": (L, H * hd, d),
        "layers/ln1": (L, d),
        "layers/ln2": (L, d),
        "layers/mlp/wi": (L, d, 2 * F),
        "layers/mlp/wo": (L, F, d),
    }
    if not a.get("tie_embeddings"):
        shapes["lm_head"] = (d, V)
    return shapes


def embed(rest: dict, tokens, a: dict):
    return rest["embed"][tokens].astype(jnp.float32)


def head(rest: dict, a: dict):
    return rest["embed"].T if a.get("tie_embeddings") else rest["lm_head"]


def rope(x, positions):
    """x (B, S, H, D): rotate the two halves of each head by position."""
    half = x.shape[-1] // 2
    freqs = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, positions, P):
    """Causal softmax attention, q (B, S, H, D), k/v (B, S, Hkv, D), computed
    one block of queries at a time (each block recomputed in the backward)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    bq = min(QUERY_BLOCK, S)
    nb = -(-S // bq)
    pad = nb * bq - S
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qpos = jnp.pad(positions, (0, pad), constant_values=-1)
    qb = qp.reshape(B, nb, bq, Hkv, g, D).transpose(1, 0, 2, 3, 4, 5)
    pb = qpos.reshape(nb, bq)

    @jax.checkpoint
    def block(args):
        qq, pp = args
        s = P.ein("bqkgd,btkd->bkgqt", qq, k) / jnp.sqrt(jnp.float32(D))
        mask = positions[None, :] <= pp[:, None]  # (bq, S)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        s = jnp.where(pp[None, None, None, :, None] < 0, 0.0, s)  # padded rows
        p = jax.nn.softmax(s, axis=-1)
        return P.ein("bkgqt,btkd->bqkgd", p, v)

    out = jax.lax.map(block, (qb, pb))  # (nb, B, bq, Hkv, g, D)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nb * bq, H, D)
    return out[:, :S]


def layer(p: dict, h, positions, a: dict, P):
    """One decoder layer; ``p`` holds this layer's leaves by path below
    ``layers/``."""
    B, S, d = h.shape
    H, Hkv = a["heads"], a["kv_heads"]
    hd = a.get("head_dim") or d // H
    x = rmsnorm(h, p["ln1"], NORM_EPS)
    qkv = P.mm(x, p["attn/wqkv"])
    q = qkv[..., :H * hd].reshape(B, S, H, hd)
    k = qkv[..., H * hd:(H + Hkv) * hd].reshape(B, S, Hkv, hd)
    v = qkv[..., (H + Hkv) * hd:].reshape(B, S, Hkv, hd)
    q, k = rope(q, positions), rope(k, positions)
    o = attention(q, k, v, positions, P).reshape(B, S, H * hd)
    h = h + P.mm(o, p["attn/wo"])
    x = rmsnorm(h, p["ln2"], NORM_EPS)
    gu = P.mm(x, p["mlp/wi"])
    F = gu.shape[-1] // 2
    return h + P.mm(silu(gu[..., :F]) * gu[..., F:], p["mlp/wo"])
