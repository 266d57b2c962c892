"""Plain float32 Mamba-2 (state-space duality) block, written from the
published description: input projection to z | x B C | dt, a causal
depthwise convolution with SiLU, the SSD recurrence
h_t = exp(A dt_t) h_{t-1} + dt_t x_t B_t^T, y_t = C_t h_t + D x_t computed
in its chunked (quadratic within a chunk, linear across chunks) form, a
SiLU(z) gate and the output projection.

Where the program departs from the published block, this follows the
program and the configuration file says so: no gated RMSNorm before the
output projection, and norms with epsilon 1e-6. The weights' layout is the
program's, read by path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import rmsnorm, silu

NORM_EPS = 1e-6


def norm_eps(a: dict) -> float:
    return NORM_EPS


def _dims(a: dict):
    d = a["hidden"]
    di = a.get("ssm_expand", 2) * d
    return d, di, a["ssm_heads"], a["ssm_state"]


KIND = "ssd"
CONV_K = 4


def pattern(a: dict) -> list:
    return [KIND] * a["num_layers"]


def layer_shapes(kind: str, a: dict) -> dict:
    d, di, H, N = _dims(a)
    conv = di + 2 * N
    return {
        "ln1": (d,),
        "ssm/in_proj": (d, 2 * di + 2 * N + H),
        "ssm/conv_w": (CONV_K, conv),
        "ssm/conv_b": (conv,),
        "ssm/dt_bias": (H,),
        "ssm/A_log": (H,),
        "ssm/D": (H,),
        "ssm/out_proj": (di, d),
    }


def rest_shapes(a: dict) -> dict:
    d, V = a["hidden"], a["vocab"]
    shapes = {"embed": (V, d), "final_norm": (d,)}
    if not a.get("tie_embeddings"):
        shapes["lm_head"] = (d, V)
    return shapes


def matmul_params(kind: str, a: dict) -> int:
    d, di, H, N = _dims(a)
    return d * (2 * di + 2 * N + H) + di * d


def mixer_flops(kind: str, a: dict, keys) -> float:
    """None of the SSD's products is a floor for every form of it: the
    sequential form updates its state elementwise and contracts only the
    read-out, the chunked form at the published chunk size computes
    several times more products."""
    return 0.0


def kv_entries(kind: str, a: dict) -> int:
    return 0


def decode_state_bytes(kind: str, a: dict, batch: int, cache_bytes: int) -> int:
    """The (batch, heads, head_dim, state) float32 SSD state and the last
    CONV_K - 1 convolution inputs in the cache's dtype, each read and
    written once."""
    d, di, H, N = _dims(a)
    ssd_state = batch * H * (di // H) * N * 4
    conv = batch * (CONV_K - 1) * (di + 2 * N) * cache_bytes
    return 2 * (ssd_state + conv)


def embed(rest: dict, tokens, a: dict):
    return rest["embed"][tokens].astype(jnp.float32)


def head(rest: dict, a: dict):
    return rest["embed"].T if a.get("tie_embeddings") else rest["lm_head"]


def causal_conv(x, w, b):
    """Depthwise causal convolution along the sequence: x (B, S, C), w (K, C);
    out_t = b + sum_j w[K-1-j] x_{t-j}."""
    K = w.shape[0]
    S = x.shape[1]
    out = b
    for j in range(K):
        shifted = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :S]
        out = out + shifted * w[K - 1 - j]
    return out


def segsum(x):
    """(..., T) -> (..., T, T) with [i, j] = x[j+1] + ... + x[i] for j <= i,
    -inf above the diagonal (summed directly, not as a difference)."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., :, None], x.shape + (T,))
    xx = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), xx, 0.0)
    s = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def ssd(X, dA, Bm, C, chunk: int, P):
    """Chunked SSD with a zero initial state. X (b, l, h, p) = dt * x,
    dA (b, l, h) = dt * A, Bm/C (b, l, n) shared by every head."""
    b, l, h, p = X.shape
    n = Bm.shape[-1]
    q = min(chunk, l)
    c = l // q
    X = X.reshape(b, c, q, h, p)
    dA = dA.reshape(b, c, q, h).transpose(0, 3, 1, 2)  # (b, h, c, q)
    Bm = Bm.reshape(b, c, q, n)
    C = C.reshape(b, c, q, n)
    cum = jnp.cumsum(dA, axis=-1)
    Lmat = jnp.exp(segsum(dA))  # (b, h, c, q, q)
    y_diag = P.ein("bcln,bcsn,bhcls,bcshp->bclhp", C, Bm, Lmat, X)
    decay_states = jnp.exp(cum[..., -1:] - cum)
    states = P.ein("bcln,bhcl,bclhp->bchpn", Bm, decay_states, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(segsum(jnp.pad(cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = P.ein("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    y_off = P.ein("bcln,bchpn,bhcl->bclhp", C, states, jnp.exp(cum))
    return (y_diag + y_off).reshape(b, l, h, p)


def layer(kind: str, p: dict, h, positions, a: dict, P):
    """One Mamba-2 layer; ``p`` holds its leaves by ``layer_shapes`` path."""
    B, S, _ = h.shape
    d, di, H, N = _dims(a)
    x = rmsnorm(h, p["ln1"], NORM_EPS)
    zxbcdt = P.mm(x, p["ssm/in_proj"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * N]
    dt = jax.nn.softplus(zxbcdt[..., 2 * di + 2 * N:] + p["ssm/dt_bias"])  # (B, S, H)
    xbc = silu(causal_conv(xbc, p["ssm/conv_w"], p["ssm/conv_b"]))
    xs = xbc[..., :di].reshape(B, S, H, di // H)
    Bm, C = xbc[..., di:di + N], xbc[..., di + N:]
    A = -jnp.exp(p["ssm/A_log"])
    y = ssd(xs * dt[..., None], dt * A, Bm, C, a.get("ssm_chunk", 256), P)
    y = y + p["ssm/D"][None, None, :, None] * xs
    y = y.reshape(B, S, di) * silu(z)
    return h + P.mm(y, p["ssm/out_proj"])
