"""Numerics parity for every §Perf lowering optimization (EXPERIMENTS.md):
the optimized lowerings must be bit-compatible (to float tolerance) with the
baseline paths they replace."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.models import lm
from repro.train.optimizer import adamw_init
from repro.train.train_step import TrainStepCfg, make_train_step

CFG = lm.ModelCfg(dtype=jnp.float32, attn_impl="xla", ssm_impl="xla")


@pytest.fixture(scope="module")
def setup():
    arch = get_reduced("qwen3-8b")
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, arch.vocab)
    full = lm.forward_logits(params, arch, CFG, {"tokens": toks})
    return arch, params, toks, full


def _serve_roundtrip(arch, params, toks, cfg):
    B, S = toks.shape
    caches = lm.init_caches(arch, cfg, B, S)
    # the prefill's path with the logits of every prompt position
    lg_pre, caches = lm.forward_cached(params, arch, cfg, caches, toks[:, : S - 1], 0)
    lg_dec, _ = lm.decode_step(params, arch, cfg, caches, toks[:, S - 1 :], S - 1)
    return lg_pre, lg_dec


@pytest.mark.parametrize("opts", [
    {"decode_dense_attn": True},
    {"kv_scatter_write": True},
    {"kv_cache_repeat": 2},
    {"decode_dense_attn": True, "kv_scatter_write": True},
    {"decode_dense_attn": True, "kv_cache_repeat": 2},
])
def test_serve_opts_parity(setup, opts):
    arch, params, toks, full = setup
    cfg = dataclasses.replace(CFG, **opts)
    lg_pre, lg_dec = _serve_roundtrip(arch, params, toks, cfg)
    S = toks.shape[1]
    assert float(jnp.abs(lg_pre - full[:, : S - 1]).max()) < 1e-4
    assert float(jnp.abs(lg_dec[:, 0] - full[:, S - 1]).max()) < 1e-4


@pytest.mark.parametrize("extra", [
    {}, {"kv_scatter_write": True, "decode_dense_attn": True},
])
def test_int8_kv_cache_parity_within_quant_error(setup, extra):
    """§Perf B6: int8 KV with per-(token, head) scales — logits must stay
    within ~2% relative of the bf16-cache path."""
    arch, params, toks, full = setup
    cfg = dataclasses.replace(CFG, kv_cache_quant=True, **extra)
    lg_pre, lg_dec = _serve_roundtrip(arch, params, toks, cfg)
    S = toks.shape[1]
    scale = float(jnp.abs(full[:, S - 1]).max())
    assert float(jnp.abs(lg_dec[:, 0] - full[:, S - 1]).max()) / scale < 0.02
    scale_pre = float(jnp.abs(full[:, : S - 1]).max())
    assert float(jnp.abs(lg_pre - full[:, : S - 1]).max()) / scale_pre < 0.02


def test_pre_cast_identical_loss(setup):
    arch, params, toks, _ = setup
    batch = {"tokens": jnp.tile(toks, (4, 1))}
    outs = {}
    for pc in (False, True):
        cfg = TrainStepCfg(num_microbatches=4, pre_cast=pc)
        _, _, m = make_train_step(arch, CFG, cfg)(params, adamw_init(params), batch)
        outs[pc] = float(m["loss"])
    assert outs[True] == pytest.approx(outs[False], rel=1e-6)


def test_act_shard_constraints_are_noop_numerically(setup):
    """with_sharding_constraint changes layout, never values — on a 1-device
    mesh the constrained forward must match exactly."""
    arch, params, toks, full = setup
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = dataclasses.replace(
        CFG, act_shard={"batch": ("data",), "model": "model"}
    )
    with jax.set_mesh(mesh):
        out = lm.forward_logits(params, arch, cfg, {"tokens": toks})
    assert float(jnp.abs(out - full).max()) == 0.0


def test_hybrid_serve_opts_parity():
    """Ring-cache (sliding window) interacts with scatter writes."""
    arch = get_reduced("hymba-1.5b")
    arch = dataclasses.replace(arch, sliding_window=6)
    params = lm.init_params(arch, jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 14), 0, arch.vocab)
    full = lm.forward_logits(params, arch, CFG, {"tokens": toks})
    cfg = dataclasses.replace(CFG, kv_scatter_write=True, decode_dense_attn=True)
    caches = lm.init_caches(arch, cfg, 1, 14)
    _, caches = lm.prefill(params, arch, cfg, caches, toks[:, :10])
    for i in range(10, 14):
        lg, caches = lm.decode_step(params, arch, cfg, caches, toks[:, i : i + 1], i)
        assert float(jnp.abs(lg[:, 0] - full[:, i]).max()) < 1e-4, i
