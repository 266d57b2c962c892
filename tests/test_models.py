"""Per-arch smoke tests (reduced configs) + serve parity for every family.

The assignment requires: instantiate a REDUCED config of each assigned
architecture's family and run one forward/train step on CPU asserting
output shapes + no NaNs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED, EXTRA, get_arch, get_reduced
from repro.models import lm
from repro.models.ssm import CONV_K

CFG = lm.ModelCfg(dtype=jnp.float32, attn_impl="xla", ssm_impl="xla")


def _batch(arch, B=2, S=16, seed=0):
    key = jax.random.PRNGKey(seed)
    batch = {"tokens": jax.random.randint(key, (B, S), 0, arch.vocab)}
    if arch.family == "encdec":
        batch["enc_features"] = jax.random.normal(
            key, (B, arch.encoder_seq, arch.hidden)
        )
    elif arch.frontend_stub and arch.frontend_seq:
        batch["frontend"] = jax.random.normal(key, (B, arch.frontend_seq, arch.hidden))
    return batch


@pytest.mark.parametrize("name", ASSIGNED)
def test_reduced_smoke_forward_and_train_step(name):
    arch = get_reduced(name)
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    batch = _batch(arch)
    logits = lm.forward_logits(params, arch, CFG, batch)
    S_total = batch["tokens"].shape[1] + (
        arch.frontend_seq if arch.frontend_stub and "frontend" in batch else 0
    )
    assert logits.shape == (2, S_total, arch.vocab)
    assert bool(jnp.isfinite(logits).all())
    loss, metrics = lm.forward_train(params, arch, CFG, batch)
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: lm.forward_train(p, arch, CFG, batch)[0])(params)
    gn = jax.tree_util.tree_reduce(
        lambda a, g: a & bool(jnp.isfinite(g).all()), grads, True
    )
    assert gn


@pytest.mark.parametrize("name", ASSIGNED)
def test_full_config_matches_spec(name):
    """The full configs are exercised only via the dry-run; here we pin the
    published numbers so a config edit can't silently drift."""
    arch = get_arch(name)
    spec = {
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 49155),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 202048),
        "qwen3-32b": (64, 5120, 64, 8, 151936),
        "yi-6b": (32, 4096, 32, 4, 64000),
        "command-r-35b": (40, 8192, 64, 8, 256000),
        "qwen3-8b": (36, 4096, 32, 8, 151936),
        "hymba-1.5b": (32, 1600, 25, 5, 32001),
        "whisper-tiny": (4, 384, 6, 6, 51865),
        "mamba2-370m": (48, 1024, 0, 0, 50280),
        "pixtral-12b": (40, 5120, 32, 8, 131072),
    }[name]
    assert (arch.num_layers, arch.hidden, arch.heads, arch.kv_heads, arch.vocab) == spec


@pytest.mark.parametrize("name", ASSIGNED + EXTRA)
def test_serve_parity_prefill_decode(name):
    """prefill + step-by-step decode == teacher forcing, per family."""
    arch = get_reduced(name)
    if arch.family == "moe":
        cfg = dataclasses.replace(CFG, capacity_factor=8.0)  # no drops
    else:
        cfg = CFG
    B, S = 2, 12
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    batch = _batch(arch, B, S)
    toks = batch["tokens"]
    full = lm.forward_logits(params, arch, cfg, batch)
    fe = batch.get("frontend")
    max_len = S + 4 + (fe.shape[1] if fe is not None else 0)
    caches = lm.init_caches(arch, cfg, B, max_len,
                            enc_features=batch.get("enc_features"), params=params)
    off = fe.shape[1] if fe is not None else 0
    # every prompt position through the cached path, and the prefill's
    # last-position logits
    every, _ = lm.forward_cached(params, arch, cfg, caches, toks[:, : S - 2], 0, frontend=fe)
    assert float(jnp.abs(every - full[:, : S - 2 + off]).max()) < 1e-4
    lg, caches = lm.prefill(params, arch, cfg, caches, toks[:, : S - 2], frontend=fe)
    assert lg.shape == (B, 1, arch.vocab)
    assert float(jnp.abs(lg[:, 0] - full[:, S - 3 + off]).max()) < 1e-4
    pos = S - 2 + off
    lg1, caches = lm.decode_step(params, arch, cfg, caches, toks[:, S - 2 : S - 1], pos)
    assert float(jnp.abs(lg1[:, 0] - full[:, pos]).max()) < 1e-4
    lg2, _ = lm.decode_step(params, arch, cfg, caches, toks[:, S - 1 : S], pos + 1)
    assert float(jnp.abs(lg2[:, 0] - full[:, pos + 1]).max()) < 1e-4


@pytest.mark.parametrize("name", ASSIGNED + EXTRA)
def test_cache_layout_per_preset(name):
    """Each family's decode caches: which, their shapes and dtypes, and the
    bytes ``cache_bytes`` counts."""
    arch = get_reduced(name)
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    B, max_len = 3, 40
    layout = lm.cache_layout(arch, cfg, B, max_len)
    if arch.layer_types:
        n_kv = sum(t == "attention" for t in arch.layer_types)
        n_ssm = sum(t == "mamba" for t in arch.layer_types)
    else:
        n_kv = 0 if arch.family == "ssm" else arch.num_layers
        n_ssm = arch.num_layers if arch.family in ("ssm", "hybrid") else 0
    assert (n_kv > 0, n_ssm > 0) == {
        "ssm": (False, True), "hybrid": (True, True), "interleaved": (True, True),
    }.get(arch.family, (True, False))
    want = {}
    if n_kv:
        T = min(max_len, arch.sliding_window) if arch.sliding_window else max_len
        want["k"] = want["v"] = ((n_kv, B, arch.kv_heads, T, arch.head_dim), jnp.bfloat16)
    if n_ssm:
        di = arch.ssm_expand * arch.hidden
        H = arch.ssm_heads or max(di // 64, 1)
        want["conv"] = ((n_ssm, B, CONV_K - 1, di + 2 * arch.ssm_state), jnp.bfloat16)
        want["state"] = ((n_ssm, B, H, di // H, arch.ssm_state), jnp.float32)
    assert {k: (s.shape, s.dtype) for k, s in layout.items()} == want
    nbytes = {k: s.size * s.dtype.itemsize for k, s in layout.items()}
    assert lm.cache_bytes(arch, cfg, B, max_len) == {
        "kv_bytes": nbytes.get("k", 0) + nbytes.get("v", 0),
        "ssm_state_bytes": nbytes.get("state", 0), "conv_bytes": nbytes.get("conv", 0)}


def test_hybrid_ring_cache_wraps_correctly():
    """Decode past the sliding window: ring cache must match full forward."""
    arch = get_reduced("hymba-1.5b")  # sliding_window=32
    arch = dataclasses.replace(arch, sliding_window=6)
    B, S = 1, 14
    params = lm.init_params(arch, jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, arch.vocab)
    full = lm.forward_logits(params, arch, CFG, {"tokens": toks})
    caches = lm.init_caches(arch, CFG, B, S)
    lg, caches = lm.prefill(params, arch, CFG, caches, toks[:, :10])
    for i in range(10, S):
        lg, caches = lm.decode_step(params, arch, CFG, caches, toks[:, i : i + 1], i)
        assert float(jnp.abs(lg[:, 0] - full[:, i]).max()) < 1e-4, i


def test_moe_routing_is_sparse_and_weighted():
    """Zeroing a never-selected expert must not change outputs."""
    arch = get_reduced("granite-moe-3b-a800m")
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    batch = _batch(arch)
    from repro.models.moe import moe_block

    lp = jax.tree_util.tree_map(lambda x: x[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, arch.hidden))
    y = moe_block(lp, x, top_k=arch.top_k, capacity_factor=8.0)
    # find the least-routed expert and zero it
    logits = x.reshape(-1, arch.hidden) @ lp["router"]
    _, sel = jax.lax.top_k(logits, arch.top_k)
    unused = [e for e in range(arch.num_experts) if not bool((sel == e).any())]
    if unused:
        e = unused[0]
        lp2 = dict(lp)
        lp2["wi"] = lp["wi"].at[e].set(0.0)
        y2 = moe_block(lp2, x, top_k=arch.top_k, capacity_factor=8.0)
        assert float(jnp.abs(y - y2).max()) == 0.0


def test_remat_does_not_change_loss_or_grads(llama7b):
    arch = get_reduced("qwen3-8b")
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    batch = _batch(arch)
    outs = {}
    for remat in ("none", "selective", "full"):
        cfg = dataclasses.replace(CFG, remat=remat)
        loss, _ = lm.forward_train(params, arch, cfg, batch)
        g = jax.grad(lambda p: lm.forward_train(p, arch, cfg, batch)[0])(params)
        outs[remat] = (float(loss), g)
    for remat in ("selective", "full"):
        assert outs[remat][0] == pytest.approx(outs["none"][0], rel=1e-6)
        err = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), outs[remat][1], outs["none"][1]
        )
        assert max(jax.tree_util.tree_leaves(err)) < 1e-5
