"""Plain float32 decoder whose layers are of two kinds by a published
pattern (``layer_types``): grouped-query attention without position
embedding (NoPE) layers among Mamba-2 layers, each layer followed by a
SwiGLU MLP. Written from the published description of Granite-4.0-H
(``granitemoehybrid`` with no experts):

* embeddings multiplied by ``embedding_multiplier``;
* each layer ``h += r * mixer(rmsnorm(h))``, then
  ``h += r * mlp(rmsnorm(h))``, r the ``residual_multiplier``;
* attention scores ``q . k`` scaled by ``attention_multiplier`` (in place of
  1 / sqrt(head_dim)), no rotary embedding;
* the Mamba-2 mixer of ``ssm.py`` (convolution with its bias, the SSD, D)
  ending in the gated RMSNorm ``rmsnorm(y * silu(z))`` with its own gain
  before the output projection;
* every RMSNorm with epsilon ``rms_norm_eps``; tied embeddings; logits
  divided by ``logits_scaling``.

There is no departure from the published description. The weights' layout
is the program's, read by path: fused q|k|v and gate|up projections, the
gated norm's gain at ``ssm/ln1`` (a name ``weights.py`` draws at one).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import dense, ssm
from chipbench.reference.common import rmsnorm, silu

KINDS = {"attention": "attn", "mamba": "ssd"}

rest_shapes = dense.rest_shapes


def norm_eps(a: dict) -> float:
    return a["rms_norm_eps"]


def pattern(a: dict) -> list:
    return [KINDS[t] for t in a["layer_types"]]


def _mlp_shapes(a: dict) -> dict:
    d, F = a["hidden"], a["ffn"]
    return {"ln1": (d,), "ln2": (d,), "mlp/wi": (d, 2 * F), "mlp/wo": (F, d)}


def layer_shapes(kind: str, a: dict) -> dict:
    if kind == "attn":
        shapes = {k: v for k, v in dense.layer_shapes(kind, a).items() if k.startswith("attn/")}
    else:
        shapes = {k: v for k, v in ssm.layer_shapes(kind, a).items() if k.startswith("ssm/")}
        shapes["ssm/ln1"] = (a.get("ssm_expand", 2) * a["hidden"],)
    return {**shapes, **_mlp_shapes(a)}


def matmul_params(kind: str, a: dict) -> int:
    if kind == "attn":
        return dense.matmul_params(kind, a)  # its MLP included
    return ssm.matmul_params(kind, a) + 3 * a["hidden"] * a["ffn"]


def mixer_flops(kind: str, a: dict, keys) -> float:
    return (dense if kind == "attn" else ssm).mixer_flops(kind, a, keys)


def kv_entries(kind: str, a: dict) -> int:
    return (dense if kind == "attn" else ssm).kv_entries(kind, a)


def decode_state_bytes(kind: str, a: dict, batch: int, cache_bytes: int) -> int:
    return (dense if kind == "attn" else ssm).decode_state_bytes(kind, a, batch, cache_bytes)


def embed(rest: dict, tokens, a: dict):
    return dense.embed(rest, tokens, a) * a["embedding_multiplier"]


def head(rest: dict, a: dict):
    """The output matrix with the logits' division folded in."""
    return dense.head(rest, a) / a["logits_scaling"]


def _attention(p: dict, x, positions, a: dict, P):
    B, S, _ = x.shape
    H, Hkv, hd = a["heads"], a["kv_heads"], a["head_dim"]
    qkv = P.mm(x, p["attn/wqkv"])
    q = qkv[..., :H * hd].reshape(B, S, H, hd)
    k = qkv[..., H * hd:(H + Hkv) * hd].reshape(B, S, Hkv, hd)
    v = qkv[..., (H + Hkv) * hd:].reshape(B, S, Hkv, hd)
    # dense.attention scales the scores by 1 / sqrt(head_dim): q carries the
    # rest of the published multiplier
    q = q * (a["attention_multiplier"] * jnp.sqrt(jnp.float32(hd)))
    o = dense.attention(q, k, v, positions, P).reshape(B, S, H * hd)
    return P.mm(o, p["attn/wo"])


def _mamba(p: dict, x, a: dict, P):
    B, S, _ = x.shape
    d, di, H, N = ssm._dims(a)
    chunk = a.get("ssm_chunk", 256)
    zxbcdt = P.mm(x, p["ssm/in_proj"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * N]
    dt = jax.nn.softplus(zxbcdt[..., 2 * di + 2 * N:] + p["ssm/dt_bias"])  # (B, S, H)
    xbc = silu(ssm.causal_conv(xbc, p["ssm/conv_w"], p["ssm/conv_b"]))
    xs = xbc[..., :di].reshape(B, S, H, di // H)
    Bm, C = xbc[..., di:di + N], xbc[..., di + N:]
    A = -jnp.exp(p["ssm/A_log"])
    # the chunked SSD takes whole chunks: positions past S are zeros, which
    # no earlier position sees
    pad = (-S) % chunk if S > chunk else 0
    padded = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))  # noqa: E731
    y = ssm.ssd(padded(xs * dt[..., None]), padded(dt * A), padded(Bm), padded(C),
                chunk, P)[:, :S]
    y = y + p["ssm/D"][None, None, :, None] * xs
    y = rmsnorm(y.reshape(B, S, di) * silu(z), p["ssm/ln1"], a["rms_norm_eps"])
    return P.mm(y, p["ssm/out_proj"])


def layer(kind: str, p: dict, h, positions, a: dict, P):
    """One layer of ``kind``; ``p`` holds its leaves by ``layer_shapes`` path."""
    eps, r = a["rms_norm_eps"], a["residual_multiplier"]
    x = rmsnorm(h, p["ln1"], eps)
    mixed = _attention(p, x, positions, a, P) if kind == "attn" else _mamba(p, x, a, P)
    h = h + r * mixed
    x = rmsnorm(h, p["ln2"], eps)
    gu = P.mm(x, p["mlp/wi"])
    F = gu.shape[-1] // 2
    return h + r * P.mm(silu(gu[..., :F]) * gu[..., F:], p["mlp/wo"])
