"""A family of two layer kinds through the harness with no harness file
edited: the count, the tree of leaves, and both reference drivers
(``train_reference``, ``decode_logits``) against a layer loop written out
here; and ``train_reference`` split over four devices against one."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import twokind_family
from chipbench import count
from chipbench import weights as W
from chipbench.check import worst_slice
from chipbench.reference import common, dense, ssm

ARCH = {"family": "twokind", "num_layers": 6, "hidden": 64, "heads": 4, "kv_heads": 2,
        "head_dim": 16, "ffn": 96, "vocab": 128, "ssm_state": 16, "ssm_heads": 4,
        "ssm_expand": 2, "ssm_chunk": 16, "tie_embeddings": False}
PATTERN = ["ssd", "ssd", "attn", "ssd", "ssd", "attn"]
HP = {"base_lr": 3e-3, "warmup_steps": 10, "total_steps": 1000}
SEED = 2**33 + 5


@pytest.fixture
def fam(monkeypatch):
    monkeypatch.setitem(sys.modules, "chipbench.reference.twokind", twokind_family)
    return twokind_family


def test_two_kinds_counted(fam):
    assert fam.pattern(ARCH) == PATTERN
    d, di, n, h = 64, 128, 16, 4
    ssd_layer = d * (2 * di + 2 * n + h) + di * d
    attn_layer = d * (4 * 16 + 2 * 2 * 16) + 4 * 16 * d + 3 * d * 96
    assert count.matmul_params(ARCH) == 4 * ssd_layer + 2 * attn_layer
    seq, head = 32, d * 128
    # only the two attention layers' mixers grow with the keys (seq / 2 of
    # them on average under the causal mask)
    fwd = 2 * (4 * ssd_layer + 2 * attn_layer + head) + 2 * 4 * (seq / 2) * 4 * 16
    assert count.train_flops_per_step(ARCH, 3, seq) == 3 * fwd * 3 * seq
    assert count.prefill_flops(ARCH, 3, seq) == fwd * 3 * seq
    flops, nbytes = count.decode_step_work(ARCH, batch=2, position=9)
    assert flops == 2 * 2 * (4 * ssd_layer + 2 * attn_layer + head) + 2 * 2 * 4 * 10 * 4 * 16
    # KV of 10 positions in the attention layers, a fixed state in the SSD ones
    kv = 2 * 2 * (2 * 2 * 16) * 10 * 2
    state = 4 * 2 * (2 * h * (di // h) * n * 4 + 2 * 3 * (di + 2 * n) * 2)
    leaves = sum(int(np.prod(s)) for k, s in common.param_shapes(fam, ARCH).items()
                 if k not in ("embed", "lm_head"))
    assert nbytes == (leaves + head + 2 * d) * 2 + kv + state


def test_leaves_stacked_per_kind(fam):
    shapes = common.param_shapes(fam, ARCH)
    assert shapes["layers/attn/attn/wqkv"] == (2, 64, (4 + 2 * 2) * 16)
    assert shapes["layers/ssd/ssm/in_proj"] == (4, 64, 2 * 128 + 2 * 16 + 4)
    assert shapes["layers/ssd/ln1"] == (4, 64) and shapes["layers/attn/ln2"] == (2, 64)
    assert {k for k in shapes if not k.startswith("layers/")} == {"embed", "final_norm", "lm_head"}
    assert common.layer_runs(PATTERN) == [("ssd", 0, 2), ("attn", 0, 1), ("ssd", 2, 2),
                                          ("attn", 1, 1)]
    # a uniform family keeps the program's layout: one kind, ``layers/<leaf>``
    one = dict(ARCH, family="dense")
    assert "layers/attn/wqkv" in common.param_shapes(dense, one)
    assert common.layer_runs(dense.pattern(one)) == [("attn", 0, 6)]


def _hand_loss(params, tokens, P):
    """Mean next-token loss over the rows, the layers applied one by one in
    the pattern's order, each taking slice i of its kind's stacks."""
    a, blocks = ARCH, {"attn": dense, "ssd": ssm}
    h = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[1])
    seen = {"attn": 0, "ssd": 0}
    for kind in PATTERN:
        pre = f"layers/{kind}/"
        lp = {k[len(pre):]: v[seen[kind]] for k, v in params.items() if k.startswith(pre)}
        h = blocks[kind].layer(kind, lp, h, pos, a, P)
        seen[kind] += 1
    h = common.rmsnorm(h, params["final_norm"], dense.NORM_EPS)
    logits = P.mm(h[:, :-1], params["lm_head"])
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(nll)


def test_decode_logits_follow_the_pattern(fam, monkeypatch):
    toks = np.random.default_rng(1).integers(0, 128, (2, 32)).astype(np.int32)
    P = common.Products()
    with jax.default_matmul_precision("highest"):
        got = common.decode_logits(fam, ARCH, SEED, toks, 0, 32, P, dtype=jnp.float32)
        struct = {k: jax.ShapeDtypeStruct(s, jnp.float32)
                  for k, s in common.param_shapes(fam, ARCH).items()}
        params = W.make(struct, SEED, ARCH["num_layers"])
        h = params["embed"][toks]
        pos = jnp.arange(32)
        seen = {"attn": 0, "ssd": 0}
        for kind in PATTERN:
            pre = f"layers/{kind}/"
            lp = {k[len(pre):]: v[seen[kind]] for k, v in params.items() if k.startswith(pre)}
            h = {"attn": dense, "ssd": ssm}[kind].layer(kind, lp, h, pos, ARCH, P)
            seen[kind] += 1
        want = P.mm(common.rmsnorm(h, params["final_norm"], 1e-6), params["lm_head"])
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * scale
    # the same layers in another order make another model
    monkeypatch.setattr(fam, "pattern", lambda a: ["attn", "ssd", "ssd", "attn", "ssd", "ssd"])
    other = common.decode_logits(fam, ARCH, SEED, toks, 0, 32, P, dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2 * scale


def test_train_reference_follows_the_pattern(fam):
    rng = np.random.default_rng(2)
    batches = [rng.integers(0, 128, (2, 32)).astype(np.int32) for _ in range(3)]
    P = common.Products()
    got = common.train_reference(fam, ARCH, SEED, batches, HP, P)
    struct = {k: jax.ShapeDtypeStruct(s, jnp.float32)
              for k, s in common.param_shapes(fam, ARCH).items()}
    params = W.make(struct, SEED, ARCH["num_layers"])
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu = zeros, zeros
    losses = []
    grad = jax.jit(jax.value_and_grad(_hand_loss), static_argnums=2)
    update = jax.jit(common.adamw_step)
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches, start=1):
            loss, grads = grad(params, jnp.asarray(tokens), P)
            losses.append(float(loss))
            if i == 1:
                first = W.slice_norms(grads)
            params, mu, nu, _ = update(params, grads, mu, nu, jnp.float32(i),
                                       jnp.float32(common.lr_at(i, HP)))
    np.testing.assert_allclose(got["losses"], losses, rtol=2e-6)
    assert worst_slice(got["grad_norms"], first) < 1e-5
    assert worst_slice(got["change_norms"], W.change_norms(params, SEED, 6)) < 1e-4
    assert len(first) == 3 + 6 * 2 + 8 * 4  # every slice of every kind


@pytest.mark.cpu_devices(4)
@pytest.mark.parametrize("family", ["dense", "ssm", "twokind"])
def test_split_reference_matches_one_device(monkeypatch, family):
    """The float32 weights, gradient sum and row gradients split over four
    devices give the one device's losses and norms to float32 rounding."""
    monkeypatch.setitem(sys.modules, "chipbench.reference.twokind", twokind_family)
    fam = {"dense": dense, "ssm": ssm, "twokind": twokind_family}[family]
    a = dict(ARCH, family=family, num_layers=4 if family != "twokind" else 6)
    devices = jax.devices()
    assert len(devices) == 4
    split = common.leaf_shardings(common.param_shapes(fam, a), devices)
    assert tuple(split["embed"].spec) == ("shard", None)  # its 128 rows
    assert tuple(split["final_norm"].spec) == ("shard",)
    rng = np.random.default_rng(3)
    batches = [rng.integers(0, 128, (2, 32)).astype(np.int32) for _ in range(3)]
    one = common.train_reference(fam, a, SEED, batches, HP, common.Products(), devices[:1])
    four = common.train_reference(fam, a, SEED, batches, HP, common.Products(), devices)
    np.testing.assert_allclose(four["losses"], one["losses"], rtol=1e-6)
    assert worst_slice(four["grad_norms"], one["grad_norms"]) < 1e-5
    assert worst_slice(four["change_norms"], one["change_norms"]) < 1e-4
