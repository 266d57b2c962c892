"""Plain float32 pieces the family references share: matrix products at
full precision (or in simulated fp8 for the control), RMSNorm, the chunked
next-token loss, AdamW with its schedule, and the two drivers that the
check runs: three training steps, and greedy-decode gaps.

Nothing here imports the program. Weights come from ``chipbench/weights.py``
and the seed, never from the program's state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale; the gradient passes
    straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


class Products:
    """Matrix products of the reference: float32 at HIGHEST precision, or,
    for the control, with every operand rounded to fp8 first."""

    def __init__(self, fp8: bool = False):
        self.fp8 = fp8

    def _in(self, x):
        x = x.astype(jnp.float32)
        return _fp8(x) if self.fp8 else x

    def mm(self, a, b):
        return jnp.matmul(self._in(a), self._in(b), precision=HIGHEST)

    def ein(self, spec, *xs):
        return jnp.einsum(spec, *[self._in(x) for x in xs], precision=HIGHEST)


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def nll_sum(h, head, targets, P: Products, chunk: int = 1024):
    """Sum over positions of -log p(target): h (T, d), head (d, V), targets
    (T,), in chunks of positions so the logits of one chunk are live."""
    T = h.shape[0]
    chunk = min(chunk, T)
    pad = (-T) % chunk
    h = jnp.pad(h, ((0, pad), (0, 0)))
    tg = jnp.pad(targets, (0, pad))
    valid = jnp.arange(T + pad) < T

    @jax.checkpoint
    def one(args):
        hc, tc, vc = args
        lg = P.mm(hc, head)
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vc, nll, 0.0))

    n = (T + pad) // chunk
    parts = jax.lax.map(one, (h.reshape(n, chunk, -1), tg.reshape(n, chunk),
                              valid.reshape(n, chunk)))
    return jnp.sum(parts)


# --- AdamW, as the program's optimizer states it ---------------------------

def lr_at(step: int, hp: dict) -> float:
    """Linear warm-up, then cosine to a tenth (step counts from 1)."""
    base, warm, total = hp["base_lr"], hp["warmup_steps"], hp["total_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * frac)))


ADAM = dict(b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, clip_norm=1.0)


def adamw_step(params, grads, mu, nu, step: int, lr: float):
    """One AdamW step over flat dicts of float32 arrays. Gradients are
    clipped to a global norm of 1; decay reaches every leaf of two or more
    dimensions (each stacked per-layer leaf among them, norm gains too, as
    the program's optimizer does). Returns (params, mu, nu, grad_norm)."""
    b1, b2, eps = ADAM["b1"], ADAM["b2"], ADAM["eps"]
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.minimum(1.0, ADAM["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    out_p, out_m, out_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k] * scale
        m = b1 * mu[k] + (1 - b1) * g
        v = b2 * nu[k] + (1 - b2) * g * g
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        if p.ndim >= 2:
            delta = delta + ADAM["weight_decay"] * p
        out_p[k], out_m[k], out_v[k] = p - lr * delta, m, v
    return out_p, out_m, out_v, gnorm


# --- the training check ------------------------------------------------------

def _stack_split(flat):
    stacked = {k: v for k, v in flat.items() if k.startswith("layers/")}
    rest = {k: v for k, v in flat.items() if not k.startswith("layers/")}
    return stacked, rest


def train_reference(fam, a: dict, seed: int, batches: list, hp: dict,
                    P: Products, device=None) -> dict:
    """Three (or ``len(batches)``) AdamW steps of the plain model from the
    seed's weights. Returns the loss of each step, the per-slice norm of the
    first raw gradient, and the per-slice norm of the parameters' change.

    Gradients are taken one row at a time (float32 activations of a whole
    batch do not fit beside the weights); the optimizer state lives on the
    host's CPU device. Norms are taken on the device, in float32 as the
    program's are, the seed's weights made again for the change."""
    device = device or jax.devices()[0]
    cpu = jax.devices("cpu")[0]
    shapes = fam.param_shapes(a)
    struct = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()}
    L = a["num_layers"]
    on_dev = W.make(struct, seed, L)
    eps = fam.norm_eps(a)

    def loss_row(params, row):
        stacked, rest = _stack_split(params)
        h = fam.embed(rest, row[None], a)
        pos = jnp.arange(row.shape[0])
        layer = jax.checkpoint(lambda hh, lp: fam.layer(lp, hh, pos, a, P))

        def body(hh, lp):
            return layer(hh, {k[len("layers/"):]: v for k, v in lp.items()}), None

        h, _ = jax.lax.scan(body, h, stacked)
        h = rmsnorm(h, rest["final_norm"], eps)
        return nll_sum(h[0, :-1], fam.head(rest, a), row[1:], P) / (row.shape[0] - 1)

    @jax.jit
    def grads_of(params, tokens):
        def body(acc, row):
            l, g = jax.value_and_grad(loss_row)(params, row)
            return (acc[0] + l, jax.tree_util.tree_map(jnp.add, acc[1], g)), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (lsum, gsum), _ = jax.lax.scan(body, (0.0, zeros), tokens)
        n = tokens.shape[0]
        return lsum / n, jax.tree_util.tree_map(lambda g: g / n, gsum)

    update = jax.jit(adamw_step)
    host = jax.device_put(on_dev, cpu)
    with jax.default_device(cpu):
        mu = jax.tree_util.tree_map(jnp.zeros_like, host)
        nu = jax.tree_util.tree_map(jnp.zeros_like, host)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches, start=1):
            loss, grads = grads_of(on_dev, jnp.asarray(tokens))
            losses.append(float(loss))
            if i == 1:
                grad_norms = W.slice_norms(grads)
            del on_dev
            grads = jax.device_put(grads, cpu)
            with jax.default_device(cpu):
                host, mu, nu, _ = update(host, grads, mu, nu, jnp.float32(i),
                                         jnp.float32(lr_at(i, hp)))
            del grads
            on_dev = jax.device_put(host, device)
    change = W.change_norms(on_dev, seed, L)
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


# --- the decode check -------------------------------------------------------

def decode_logits(fam, a: dict, seed: int, seqs: np.ndarray, first: int,
                  n_pos: int, P: Products, dtype=jnp.bfloat16) -> jax.Array:
    """Full-sequence forward over ``seqs`` (K, T), one layer at a time with
    each layer's weights made again from the seed (rounded to the served
    ``dtype``, computed in float32). Returns the logits (K, n_pos, V) at
    positions first .. first + n_pos - 1."""
    key = W.seed_key(seed)
    L = a["num_layers"]
    shapes = fam.param_shapes(a)
    served = lambda x: x.astype(dtype).astype(jnp.float32)  # noqa: E731
    T = seqs.shape[1]
    pos = jnp.arange(T)

    @jax.jit
    def start(key, tokens):
        rest = {"embed": served(W.leaf(key, "embed", shapes["embed"], L))}
        return fam.embed(rest, tokens, a)

    @jax.jit
    def one_layer(key, i, h):
        lp = {k[len("layers/"):]: served(W.leaf(key, k, s[1:], L, i))
              for k, s in shapes.items() if k.startswith("layers/")}
        return fam.layer(lp, h, pos, a, P)

    @jax.jit
    def finish(key, h):
        rest = {k: served(W.leaf(key, k, s, L)) for k, s in shapes.items()
                if not k.startswith("layers/")}
        h = rmsnorm(h[:, first:first + n_pos], rest["final_norm"], fam.norm_eps(a))
        return P.mm(h, fam.head(rest, a))

    with jax.default_matmul_precision("highest"):
        h = start(key, jnp.asarray(seqs))
        for i in range(L):
            h = one_layer(key, i, h)
        return finish(key, h)


def served_gaps(ref_logits, tokens, lens) -> np.ndarray:
    """Per request, the widest gap by which a served token's reference logit
    lies below the reference's best at its position; ``tokens`` (K, n_pos)
    are the served tokens, ``lens`` how many of them count."""
    lg = np.asarray(jax.device_get(ref_logits), np.float64)
    best = lg.max(axis=-1)
    got = np.take_along_axis(lg, np.asarray(tokens)[..., None], axis=-1)[..., 0]
    valid = np.arange(lg.shape[1])[None, :] < np.asarray(lens)[:, None]
    return np.where(valid, best - got, 0.0).max(axis=1)
