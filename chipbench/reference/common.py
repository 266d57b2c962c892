"""Plain float32 pieces the family references share: matrix products at
full precision (or in simulated fp8 for the control), RMSNorm, the chunked
next-token loss, AdamW with its schedule, the model's layers in the order
of its family's pattern, and the two drivers that the check runs: three
training steps, and greedy-decode gaps.

Nothing here imports the program. Weights come from ``chipbench/weights.py``
and the seed, never from the program's state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

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


# --- the model's layers --------------------------------------------------------

def layer_prefix(kind: str, pattern: list) -> str:
    """Where the stacked leaves of ``kind`` sit in the tree: ``layers/`` for a
    pattern of one kind, ``layers/<kind>/`` for one of several."""
    return "layers/" if len(set(pattern)) == 1 else f"layers/{kind}/"


def param_shapes(fam, a: dict) -> dict:
    """The whole tree's leaf shapes: the rest, and each kind's layer leaves
    stacked over that kind's count."""
    pattern = fam.pattern(a)
    shapes = dict(fam.rest_shapes(a))
    for kind in dict.fromkeys(pattern):
        pre = layer_prefix(kind, pattern)
        n = pattern.count(kind)
        shapes.update({pre + k: (n, *s) for k, s in fam.layer_shapes(kind, a).items()})
    return shapes


def layer_runs(pattern: list) -> list:
    """(kind, index within the kind of its first layer, count) of each run of
    consecutive layers of one kind, in order."""
    runs, seen = [], {}
    for kind in pattern:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return [tuple(r) for r in runs]


def run_layers(fam, a: dict, params: dict, h, positions, P):
    """``h`` through every layer in the pattern's order: each run of one kind
    is a scan over its slice of that kind's stacks, each layer recomputed in
    the backward."""
    pattern = fam.pattern(a)
    for kind, first, n in layer_runs(pattern):
        pre = layer_prefix(kind, pattern)
        leaves = {k[len(pre):]: jax.lax.slice_in_dim(v, first, first + n)
                  for k, v in params.items() if k.startswith(pre)}
        layer = jax.checkpoint(lambda hh, lp, kind=kind: fam.layer(kind, lp, hh, positions, a, P))
        h, _ = jax.lax.scan(lambda hh, lp, layer=layer: (layer(hh, lp), None), h, leaves)
    return h


# --- the training check ------------------------------------------------------

def leaf_shardings(shapes: dict, devices) -> dict | None:
    """Each leaf split over ``devices`` along its largest axis that their
    number divides (replicated where none does); None on one device."""
    n = len(devices)
    if n == 1:
        return None
    mesh = Mesh(np.asarray(devices), ("shard",))
    out = {}
    for k, s in shapes.items():
        axes = [i for i in sorted(range(len(s)), key=lambda i: -s[i]) if s[i] % n == 0]
        spec = [None] * len(s)
        if axes:
            spec[axes[0]] = "shard"
        out[k] = NamedSharding(mesh, PartitionSpec(*spec))
    return out


def row_grads(fam, a: dict, P: Products, sh: dict | None = None):
    """The jitted (params, tokens (rows, seq)) -> (mean loss, mean gradient),
    one row at a time; with shardings ``sh`` each row's gradient and the sum
    are split as the weights are."""
    eps = fam.norm_eps(a)

    def split(tree):
        return tree if sh is None else jax.lax.with_sharding_constraint(tree, sh)

    def loss_row(params, row):
        rest = {k: v for k, v in params.items() if not k.startswith("layers/")}
        h = fam.embed(rest, row[None], a)
        h = run_layers(fam, a, params, h, jnp.arange(row.shape[0]), P)
        h = rmsnorm(h, rest["final_norm"], eps)
        return nll_sum(h[0, :-1], fam.head(rest, a), row[1:], P) / (row.shape[0] - 1)

    @jax.jit
    def grads_of(params, tokens):
        def body(acc, row):
            l, g = jax.value_and_grad(loss_row)(params, row)
            return (acc[0] + l, jax.tree_util.tree_map(jnp.add, acc[1], split(g))), None

        zeros = split(jax.tree_util.tree_map(jnp.zeros_like, params))
        (lsum, gsum), _ = jax.lax.scan(body, (0.0, zeros), tokens)
        n = tokens.shape[0]
        return lsum / n, split(jax.tree_util.tree_map(lambda g: g / n, gsum))

    return grads_of


def train_reference(fam, a: dict, seed: int, batches: list, hp: dict,
                    P: Products, devices=None) -> dict:
    """Three (or ``len(batches)``) AdamW steps of the plain model from the
    seed's weights. Returns the loss of each step, the per-slice norm of the
    first raw gradient, and the per-slice norm of the parameters' change.

    Gradients are taken one row at a time (float32 activations of a whole
    batch do not fit beside the weights); the optimizer state lives on the
    host's CPU device. On several ``devices`` the float32 weights, the
    gradient sum and each row's gradient are split over them
    (``leaf_shardings``). Norms are taken on the devices, in float32 as the
    program's are, the seed's weights made again for the change."""
    devices = list(devices or jax.devices()[:1])
    cpu = jax.devices("cpu")[0]
    shapes = param_shapes(fam, a)
    struct = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()}
    L = a["num_layers"]
    sh = leaf_shardings(shapes, devices)
    on_dev = W.make(struct, seed, L, out_shardings=sh)
    grads_of = row_grads(fam, a, P, sh)
    # the host's state is replaced each step: its buffers are reused in place
    update = jax.jit(adamw_step, donate_argnums=(0, 2, 3))
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
            on_dev = jax.device_put(host, devices[0] if sh is None else sh)
    change = W.change_norms(on_dev, seed, L)
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


# --- the decode check -------------------------------------------------------

def decode_logits(fam, a: dict, seed: int, seqs: np.ndarray, first: int,
                  n_pos: int, P: Products, dtype=jnp.bfloat16) -> jax.Array:
    """Full-sequence forward over ``seqs`` (K, T), one layer at a time in the
    pattern's order, with each layer's weights made again from the seed
    (rounded to the served ``dtype``, computed in float32). Returns the
    logits (K, n_pos, V) at positions first .. first + n_pos - 1."""
    key = W.seed_key(seed)
    L = a["num_layers"]
    pattern = fam.pattern(a)
    shapes = param_shapes(fam, a)
    served = lambda x: x.astype(dtype).astype(jnp.float32)  # noqa: E731
    T = seqs.shape[1]
    pos = jnp.arange(T)

    @jax.jit
    def start(key, tokens):
        rest = {"embed": served(W.leaf(key, "embed", shapes["embed"], L))}
        return fam.embed(rest, tokens, a)

    @functools.partial(jax.jit, static_argnums=0)
    def one_layer(kind, key, i, h):
        pre = layer_prefix(kind, pattern)
        lp = {k[len(pre):]: served(W.leaf(key, k, s[1:], L, i))
              for k, s in shapes.items() if k.startswith(pre)}
        return fam.layer(kind, lp, h, pos, a, P)

    @jax.jit
    def finish(key, h):
        rest = {k: served(W.leaf(key, k, s, L)) for k, s in shapes.items()
                if not k.startswith("layers/")}
        h = rmsnorm(h[:, first:first + n_pos], rest["final_norm"], fam.norm_eps(a))
        return P.mm(h, fam.head(rest, a))

    with jax.default_matmul_precision("highest"):
        h = start(key, jnp.asarray(seqs))
        for kind, first_i, n in layer_runs(pattern):
            for i in range(first_i, first_i + n):
                h = one_layer(kind, key, i, h)
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
