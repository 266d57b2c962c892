"""The Granite-4.0-H-Micro reference against the program: the reference's
tree and work against the program's; the program against the float32
reference on seeded weights at reduced sizes (full-sequence logits, one
step's loss and gradients, prefill and decoding through the per-kind caches,
all compared as logits); and pins that Yi-6B's and Mamba-2's programs
compute bit for bit what they computed before the layer stack took several
kinds."""
import dataclasses
import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import count
from chipbench import weights as W
from chipbench.reference import common, interleaved

SEED = 2**31 + 77


def test_reference_tree_and_work_match_the_program():
    """The reference's leaves are the program's, by path and shape, at the
    reduced and the published sizes (3,191,396,096 parameters, the count of
    ``tests/test_granite.py``); the decode step's work counts the SSD state
    of the 36 Mamba layers and the KV of the 4 attention layers."""
    from repro.configs import get_arch, get_reduced
    from repro.models.lm import init_params

    for arch in (get_reduced("granite-4.0-h-micro"), get_arch("granite-4.0-h-micro")):
        a = dataclasses.asdict(arch)
        struct = jax.eval_shape(functools.partial(init_params, arch), jax.random.PRNGKey(0))
        shapes = common.param_shapes(interleaved, a)
        assert dict(zip(W.paths(struct), (tuple(s.shape) for s in
                                           jax.tree_util.tree_leaves(struct)))) == shapes
    assert sum(math.prod(s) for s in shapes.values()) == 3_191_396_096
    a = dataclasses.asdict(get_arch("granite-4.0-h-micro"))
    flops, nbytes = count.decode_step_work(a, 16, 2048)
    weights = (3_191_396_096 + 16 * 2048) * 2  # every leaf once, the head is the embedding
    kv = 4 * 16 * (2 * 8 * 64) * 2049 * 2
    state = 36 * 2 * (16 * 64 * 64 * 128 * 4 + 16 * 3 * 4352 * 2)
    assert nbytes == weights + kv + state
    matmul = 36 * (2048 * 8512 + 4096 * 2048) + 4 * (2048 * 3072 + 2048 * 2048) \
        + 40 * 3 * 2048 * 8192
    assert count.matmul_params(a) == matmul
    assert flops == 2 * 16 * (matmul + 2048 * 100352) + 16 * 4 * 4 * 2049 * 32 * 64


@pytest.fixture(scope="module")
def small():
    from repro.configs import get_reduced
    from repro.models.lm import init_params

    arch = get_reduced("granite-4.0-h-micro")
    struct = jax.eval_shape(functools.partial(init_params, arch), jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, arch.vocab, (2, 40)).astype(np.int32)
    return arch, dataclasses.asdict(arch), W.make(struct, SEED, arch.num_layers), toks


def _f32():
    from repro.models.lm import ModelCfg

    return ModelCfg(dtype=jnp.float32, attn_impl="xla", ssm_impl="xla")


def test_full_sequence_logits_match_the_reference(small):
    """Float32 program and float32 reference compute the same model: they
    differ by summation order, about 1e-7 of the largest logit (5e-6 allows
    for 40 positions through six layers); the control's fp8 products land
    near 1e-2 of it."""
    from repro.models.lm import forward_logits

    arch, a, p, toks = small
    with jax.default_matmul_precision("highest"):
        prog = forward_logits(p, arch, _f32(), {"tokens": jnp.asarray(toks)})
        ref = common.decode_logits(interleaved, a, SEED, toks, 0, 40, common.Products(),
                                   dtype=jnp.float32)
        ctl = common.decode_logits(interleaved, a, SEED, toks, 0, 40,
                                   common.Products(fp8=True), dtype=jnp.float32)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(prog - ref))) < 5e-6 * scale
    assert float(jnp.max(jnp.abs(ctl - ref))) > 3e-3 * scale


def test_train_loss_and_gradients_match_the_reference(small):
    """One step's loss and every leaf's gradient, float32 against the
    reference's row by row gradients: the loss to 1e-6 (measured 1e-7), each
    leaf to 1e-4 of its largest entry (measured 2e-6)."""
    from repro.models.lm import forward_train

    arch, a, p, toks = small
    with jax.default_matmul_precision("highest"):
        loss, g = jax.jit(jax.value_and_grad(
            lambda q, t: forward_train(q, arch, _f32(), {"tokens": t})[0]))(p, jnp.asarray(toks))
        flat = dict(zip(W.paths(p), jax.tree_util.tree_leaves(p)))
        ref_loss, ref_g = common.row_grads(interleaved, a, common.Products())(
            flat, jnp.asarray(toks))
    assert abs(float(loss) - float(ref_loss)) < 1e-6 * abs(float(ref_loss))
    got = dict(zip(W.paths(g), jax.tree_util.tree_leaves(g)))
    assert set(got) == set(ref_g)
    for k, r in ref_g.items():
        assert float(jnp.max(jnp.abs(r))) > 0, k  # every leaf takes part
        assert float(jnp.max(jnp.abs(got[k] - r))) < 1e-4 * float(jnp.max(jnp.abs(r))), k


@pytest.mark.parametrize("dtype,tol", [
    # float32 weights and caches: summation order, as the full pass
    ("float32", 5e-6),
    # the cell's bfloat16: weights, activations and the KV and conv caches
    # rounded to 8 bits of mantissa (measured 2.7e-3 of the largest logit);
    # the fp8 control reads 1.2e-2 at these sizes
    ("bfloat16", 6e-3),
])
def test_prefill_and_decode_match_the_reference(small, dtype, tol):
    """Prefill of 30 positions, then ten decode steps through the per-kind
    caches, each position's logits against the reference's full forward pass
    over the same tokens, with the weights rounded as the cell serves them."""
    from repro.models import lm

    arch, a, _, toks = small
    dt = jnp.dtype(dtype)
    cfg = lm.ModelCfg(dtype=dt, attn_impl="xla", ssm_impl="xla")
    struct = jax.eval_shape(functools.partial(lm.init_params, arch, dtype=dt),
                            jax.random.PRNGKey(0))
    p = W.make(struct, SEED, arch.num_layers)
    prefill = jax.jit(functools.partial(lm.prefill, arch=arch, cfg=cfg))
    decode = jax.jit(functools.partial(lm.decode_step, arch=arch, cfg=cfg))
    with jax.default_matmul_precision("highest"):
        ref = common.decode_logits(interleaved, a, SEED, toks, 29, 11, common.Products(),
                                   dtype=dt)
        lg, caches = prefill(p, caches=lm.init_caches(arch, cfg, 2, 40),
                             tokens=jnp.asarray(toks[:, :30]))
        got = [lg[:, 0]]
        for i in range(30, 40):
            lg, caches = decode(p, caches=caches, tokens=jnp.asarray(toks[:, i:i + 1]),
                                position=i)
            got.append(lg[:, 0])
    prog = jnp.stack(got, axis=1).astype(jnp.float32)
    err = jnp.max(jnp.abs(prog - ref), axis=-1) / jnp.max(jnp.abs(ref), axis=-1)
    assert float(jnp.max(err)) < tol


# --- Yi-6B and Mamba-2, as they computed before ------------------------------

def _digest(x) -> str:
    return hashlib.sha256(np.asarray(jax.device_get(x)).tobytes()).hexdigest()[:16]


# bfloat16 programs at the reduced presets on the seed's weights, each read
# before the layer stack took several kinds: sha256 prefixes of the full
# forward's logits, the gradient (all leaves, float32, in tree order), the
# prefill's last-position logits and caches, and four decode steps' logits;
# the loss as float.hex. (Float32 programs are not pinned: the CPU's
# threaded float32 products change their last bits with the host's cores.)
PINS = {
    "yi-6b": {
        "logits": "32264ff52d607e3a", "loss": "0x1.568bc60000000p+2",
        "grads": "206d5cc77056c24b", "prefill_last": "4a90bb925ae69487",
        "prefill_caches": {"k": "b1664e01ed170a76", "v": "632c9c035450703d"},
        "decode": ["ebbaf7336769bb2f", "01f5aa545d7b08ee", "70872a98cfc63e25",
                   "4e89a1302b8770b2"]},
    "mamba2-370m": {
        "logits": "ffeb7c3ffaed2a0a", "loss": "0x1.bf35320000000p+2",
        "grads": "f95d6fd640c04367", "prefill_last": "5da1f1b38220a990",
        "prefill_caches": {"conv": "8b898e312eb7c7c0", "state": "420dd5a4bd736273"},
        "decode": ["d0644e9cfdf06c0b", "05afd1e194af62b6", "1740da75f402fe8b",
                   "ea0d7681d316f24e"]},
}


@pytest.mark.parametrize("preset,tie", [("yi-6b", False), ("mamba2-370m", True)])
def test_uniform_families_compute_as_before(preset, tie):
    from repro.configs import get_reduced
    from repro.models import lm

    arch = dataclasses.replace(get_reduced(preset), tie_embeddings=tie)
    cfg = lm.ModelCfg(dtype=jnp.bfloat16, attn_impl="xla", ssm_impl="xla")
    seed = 2**31 + 12345
    struct = jax.eval_shape(functools.partial(lm.init_params, arch), jax.random.PRNGKey(0))
    params = W.make(struct, seed, arch.num_layers)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, arch.vocab, (2, 24)).astype(np.int32))
    got = {"logits": _digest(jax.jit(
        lambda p, t: lm.forward_logits(p, arch, cfg, {"tokens": t}))(params, toks))}
    loss, g = jax.jit(jax.value_and_grad(
        lambda p, t: lm.forward_train(p, arch, cfg, {"tokens": t})[0]))(params, toks)
    got["loss"] = float(loss).hex()
    got["grads"] = _digest(jnp.concatenate(
        [x.ravel().astype(jnp.float32) for x in jax.tree_util.tree_leaves(g)]))
    sstruct = jax.eval_shape(functools.partial(lm.init_params, arch, dtype=jnp.bfloat16),
                             jax.random.PRNGKey(0))
    sp = W.make(sstruct, seed, arch.num_layers)
    pre = jax.jit(functools.partial(lm.prefill, arch=arch, cfg=cfg))
    dec = jax.jit(functools.partial(lm.decode_step, arch=arch, cfg=cfg))
    lg, caches = pre(sp, caches=lm.init_caches(arch, cfg, 2, 24), tokens=toks[:, :20])
    got["prefill_last"] = _digest(lg[:, -1])
    got["prefill_caches"] = {k: _digest(v) for k, v in sorted(caches.items())}
    got["decode"] = []
    for i in range(20, 24):
        lg, caches = dec(sp, caches=caches, tokens=toks[:, i:i + 1], position=i)
        got["decode"].append(_digest(lg))
    assert got == PINS[preset]
