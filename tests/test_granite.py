"""Granite-4.0-H-Micro in the program: an interleaved model of Mamba-2 and
NoPE attention layers. Its preset as published (parameters counted by layer
kind), the per-kind parameter tree and caches, prefill and decode through
those caches against the full forward pass, the names its work runs under,
the engine's cache bytes on its ``serve.init_caches`` span, and the presets
of the families that were there before, unchanged."""
import collections
import functools
import glob
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_arch, get_reduced
from repro.core.arch import ModelArch
from repro.kernels import ops, ref
from repro.models import lm
from repro.serve import ServeEngine
from repro.train.optimizer import adamw_init
from repro.train.train_step import TrainStepCfg, make_train_step

CFG = lm.ModelCfg(dtype=jnp.float32, attn_impl="xla", ssm_impl="xla")


def test_published_parameter_count():
    """The counts of the published config.json (hf ibm-granite/granite-4.0-h-micro):
    a Mamba layer is in_proj 2048 x (2 x 4096 + 2 x 128 + 64), the width-4
    convolution over 4352 channels and its bias, dt_bias, A_log and D of
    64 heads, the gated norm's 4096, out_proj 4096 x 2048; an attention
    layer is q and o 2048 x 2048, k and v 2048 x 512; each has the MLP
    3 x 2048 x 8192 and two norms; then the tied 100352 x 2048 embedding and
    the final norm."""
    a = get_arch("granite-4.0-h-micro")
    mlp_norms = 3 * 2048 * 8192 + 2 * 2048
    mamba = 2048 * 8512 + 5 * 4352 + 3 * 64 + 4096 + 4096 * 2048 + mlp_norms
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + mlp_norms
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert sum(a.kind_params("mamba").values()) == mamba
    assert sum(a.kind_params("attention").values()) == attention
    total = 36 * mamba + 4 * attention + 100352 * 2048 + 2048
    assert total == 3_191_396_096
    assert a.total_params() == a.total_active_params() == total
    # the program's tree holds exactly those parameters
    tree = jax.eval_shape(functools.partial(lm.init_params, a), jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree)) == total


def test_layer_runs_follow_the_pattern():
    a = get_arch("granite-4.0-h-micro")
    assert [i for i, t in enumerate(a.layer_types) if t == "attention"] == [5, 15, 25, 35]
    assert lm.layer_runs(a) == [("ssd", 0, 5), ("attn", 0, 1), ("ssd", 5, 9), ("attn", 1, 1),
                                ("ssd", 14, 9), ("attn", 2, 1), ("ssd", 23, 9), ("attn", 3, 1),
                                ("ssd", 32, 4)]
    assert lm.layer_runs(get_reduced("granite-4.0-h-micro")) == [
        ("ssd", 0, 2), ("attn", 0, 1), ("ssd", 2, 1), ("attn", 1, 1), ("ssd", 3, 1)]
    # a configuration file gives a list: the arch keeps a tuple, and hashes
    listed = ModelArch(**{**a.__dict__, "layer_types": list(a.layer_types)})
    assert listed == a and hash(listed) == hash(a)


def test_presets_of_other_families_unchanged():
    """Yi-6B's and Mamba-2's presets are the ModelArch they were, and no
    preset of the other families takes a setting the interleaved family
    brings."""
    assert get_arch("yi-6b") == ModelArch(
        name="yi-6b", family="dense", num_layers=32, hidden=4096, heads=32, kv_heads=4,
        ffn=11008, vocab=64000)
    assert get_reduced("yi-6b") == ModelArch(
        name="yi-6b-reduced", family="dense", num_layers=2, hidden=128, heads=8, kv_heads=1,
        ffn=320, vocab=128)
    assert get_arch("mamba2-370m") == ModelArch(
        name="mamba2-370m", family="ssm", num_layers=48, hidden=1024, heads=0, kv_heads=0,
        ffn=0, vocab=50280, ssm_state=128, ssm_heads=32)
    assert get_reduced("mamba2-370m") == ModelArch(
        name="mamba2-reduced", family="ssm", num_layers=2, hidden=128, heads=0, kv_heads=0,
        ffn=0, vocab=128, ssm_state=16, ssm_heads=4)
    defaults = dict(layer_types=(), rms_norm_eps=1e-6, attention_multiplier=None,
                    embedding_multiplier=1.0, residual_multiplier=1.0, logits_scaling=1.0,
                    rope=True, ssm_gated_norm=False)
    for name in ASSIGNED:
        for arch in (get_arch(name), get_reduced(name)):
            assert {k: getattr(arch, k) for k in defaults} == defaults, name


def test_wire_format_is_sparse():
    """A spec of a model with one kind of layer serializes its arch without
    the interleaved family's fields, so its wire bytes and cache key are
    what they were before those fields; an interleaved arch goes through the
    wire and back whole."""
    from repro.core.arch import _SPARSE_FIELDS
    from repro.core.spec import FixedPool, SearchSpec, Workload

    yi = get_arch("yi-6b")
    assert not set(yi.to_dict()) & set(_SPARSE_FIELDS)
    assert ModelArch(**yi.to_dict()) == yi
    granite = get_arch("granite-4.0-h-micro")
    assert set(granite.to_dict()) >= set(_SPARSE_FIELDS)
    spec = SearchSpec(arch=granite, pool=FixedPool(device="A100", num_devices=4),
                      workload=Workload(global_batch=8, seq=1024))
    assert SearchSpec.from_json(spec.to_json()).arch == granite


def test_per_kind_tree_and_caches():
    arch = get_reduced("granite-4.0-h-micro")
    params = jax.eval_shape(functools.partial(lm.init_params, arch), jax.random.PRNGKey(0))
    layers = params["layers"]
    assert set(layers) == {"ssd", "attn"}
    assert set(layers["ssd"]) == {"ssm", "mlp", "ln1", "ln2"}
    assert set(layers["attn"]) == {"attn", "mlp", "ln1", "ln2"}
    assert layers["ssd"]["ssm"]["in_proj"].shape == (4, 128, 2 * 256 + 2 * 16 + 4)
    assert layers["ssd"]["ssm"]["ln1"].shape == (4, 256)  # the gated norm's gain
    assert layers["attn"]["attn"]["wqkv"].shape == (2, 128, (4 + 2 * 2) * 32)
    assert "lm_head" not in params  # tied
    caches = lm.init_caches(arch, CFG, 3, 20)
    # KV for the two attention layers only, conv and SSD state for the four
    # Mamba layers only
    assert {k: v.shape for k, v in caches.items()} == {
        "k": (2, 3, 2, 20, 32), "v": (2, 3, 2, 20, 32),
        "conv": (4, 3, 3, 256 + 2 * 16), "state": (4, 3, 4, 64, 16)}
    assert caches["state"].dtype == jnp.float32
    assert lm.cache_bytes(arch, CFG, 3, 20) == {
        "kv_bytes": caches["k"].nbytes + caches["v"].nbytes,
        "ssm_state_bytes": caches["state"].nbytes, "conv_bytes": caches["conv"].nbytes}
    # the whole model's caches at the benchmark's batch and length
    full = lm.cache_bytes(get_arch("granite-4.0-h-micro"),
                          lm.ModelCfg(dtype=jnp.bfloat16), 16, 2432)
    assert full == {"kv_bytes": 2 * 4 * 16 * 8 * 2432 * 64 * 2,
                    "ssm_state_bytes": 36 * 16 * 64 * 64 * 128 * 4,
                    "conv_bytes": 36 * 16 * 3 * 4352 * 2}


@pytest.mark.parametrize("dtype,tol", [
    # float32: the same products in another order
    (jnp.float32, 1e-4),
    # bfloat16: the cached path rounds its state and activations where the
    # full pass rounds others, about 2^-8 of the logits' largest each
    (jnp.bfloat16, 3e-2),
])
def test_prefill_and_decode_match_full_forward(dtype, tol):
    arch = get_reduced("granite-4.0-h-micro")
    cfg = lm.ModelCfg(dtype=dtype, attn_impl="xla", ssm_impl="xla")
    params = lm.init_params(arch, jax.random.PRNGKey(0), dtype=dtype)
    B, S, P = 2, 40, 24
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, arch.vocab)
    full = lm.forward_logits(params, arch, cfg, {"tokens": toks}).astype(jnp.float32)
    scale = float(jnp.abs(full).max())
    caches = lm.init_caches(arch, cfg, B, S)
    prefill = jax.jit(functools.partial(lm.prefill, arch=arch, cfg=cfg))
    decode = jax.jit(functools.partial(lm.decode_step, arch=arch, cfg=cfg))
    lg, caches = prefill(params, caches=caches, tokens=toks[:, :P])
    assert lg.shape == (B, 1, arch.vocab)
    assert float(jnp.abs(lg[:, 0] - full[:, P - 1]).max()) < tol * scale
    for i in range(P, S):
        lg, caches = decode(params, caches=caches, tokens=toks[:, i:i + 1], position=i)
        assert float(jnp.abs(lg[:, 0] - full[:, i]).max()) < tol * scale, i


def test_multipliers_and_norm_change_the_model():
    """Each published setting is in the forward pass: with any one of them
    set back to its default the logits move."""
    arch = get_reduced("granite-4.0-h-micro")
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, arch.vocab)
    base = lm.forward_logits(params, arch, CFG, {"tokens": toks})
    for field in ("rms_norm_eps", "attention_multiplier", "embedding_multiplier",
                  "residual_multiplier", "logits_scaling", "rope", "ssm_gated_norm"):
        default = ModelArch.__dataclass_fields__[field].default
        other = arch.__class__(**{**arch.__dict__, field: default})
        p = params
        if field == "ssm_gated_norm":  # its gain leaves the tree
            p = jax.tree_util.tree_map(lambda x: x, params)
            del p["layers"]["ssd"]["ssm"]["ln1"]
        moved = float(jnp.abs(lm.forward_logits(p, other, CFG, {"tokens": toks}) - base).max())
        assert moved > 1e-6, field


def _scope_names(hlo_text: str) -> set:
    return {re.sub(r"^(?:[\w.]+\()+|\)+$", "", part)
            for path in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in path.split("/")}


def test_work_runs_under_the_layer_scopes():
    """The train step, the prefill and the decode step of an interleaved
    model keep each kind's work under the scopes the other families use."""
    arch = get_reduced("granite-4.0-h-micro")
    cfg = lm.ModelCfg(dtype=jnp.bfloat16, attn_impl="xla", ssm_impl="xla", remat="full")
    scopes = {"embed", "attn", "ffn", "ssd", "head"}
    step = make_train_step(arch, cfg, TrainStepCfg(num_microbatches=2))
    params = jax.eval_shape(functools.partial(lm.init_params, arch), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    text = jax.jit(step).lower(params, jax.eval_shape(adamw_init, params), batch).compile().as_text()
    assert _scope_names(text) >= scopes | {"optimizer"}
    sparams = jax.eval_shape(functools.partial(lm.init_params, arch, dtype=jnp.bfloat16),
                             jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: lm.init_caches(arch, cfg, 2, 24))
    pre = jax.jit(functools.partial(lm.prefill, arch=arch, cfg=cfg)).lower(
        sparams, caches=caches, tokens=jax.ShapeDtypeStruct((2, 16), jnp.int32))
    dec = jax.jit(functools.partial(lm.decode_step, arch=arch, cfg=cfg)).lower(
        sparams, caches=caches, tokens=jax.ShapeDtypeStruct((2, 1), jnp.int32),
        position=jax.ShapeDtypeStruct((), jnp.int32))
    for lowered in (pre, dec):
        assert _scope_names(lowered.compile().as_text()) >= scopes


def test_engine_generates_and_spans_cache_bytes(tmp_path):
    """``generate`` on an interleaved model: the greedy tokens are the full
    forward pass's, and ``serve.init_caches`` carries the bytes of each kind
    of cache."""
    from jax.profiler import ProfileData

    arch = get_reduced("granite-4.0-h-micro")
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    engine = ServeEngine(arch, CFG, params, max_len=24)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, arch.vocab))
    engine.generate(prompts, max_new_tokens=3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = engine.generate(prompts, max_new_tokens=3)
    finally:
        jax.profiler.stop_trace()
    seq = res.tokens
    for i in range(3):
        full = lm.forward_logits(params, arch, CFG, {"tokens": jnp.asarray(seq[:, :8 + i])})
        assert np.array_equal(np.asarray(jnp.argmax(full[:, -1], -1)), seq[:, 8 + i])
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    spans = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("serve.init_caches", "serve.prefill"):
                    spans[ev.name].append(dict(ev.stats))
    assert spans["serve.init_caches"] == [lm.cache_bytes(arch, CFG, 2, 24)]
    assert spans["serve.prefill"] == [{"ssd_segments": lm.prefill_ssd_segments(arch, 8)}]


def test_prefill_ssd_segments():
    """Trips of the prefill's segment scan: SSD layers x ceil(S / chunk),
    none for a model without SSD layers or for a single position."""
    granite = get_arch("granite-4.0-h-micro")
    assert lm.prefill_ssd_segments(granite, 2048) == 36 * 8 == 288
    assert lm.prefill_ssd_segments(granite, 2049) == 36 * 9
    small = get_reduced("granite-4.0-h-micro")  # 4 Mamba layers, chunk 16
    assert [lm.prefill_ssd_segments(small, s) for s in (1, 8, 16, 17, 40)] == [0, 4, 4, 8, 12]
    assert lm.prefill_ssd_segments(get_reduced("mamba2-370m"), 300) == 2 * 2
    assert lm.prefill_ssd_segments(get_arch("yi-6b"), 512) == 0


def test_engine_prefill_matches_the_sequential_scan(monkeypatch):
    """``generate`` over a prompt of several segments, the last one partial,
    gives the greedy tokens of the same engine with its prefill's SSD forced
    through the sequential oracle, and its prefill the same logits. The
    embedding is scaled down so that the layers, not the embedding x12 of a
    repeated token, set the greedy tokens (a scan whose segments each start
    from zero changes them)."""
    arch = get_reduced("granite-4.0-h-micro")
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    params["embed"] = params["embed"] * 0.03
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, arch.vocab))

    def run():
        engine = ServeEngine(arch, CFG, params, max_len=48)
        logits, _ = engine._prefill(params, caches=lm.init_caches(arch, CFG, 2, 48),
                                    tokens=jnp.asarray(prompts))
        return engine.generate(prompts, max_new_tokens=8).tokens, logits

    tokens, logits = run()

    def sequential(x, dt, A, Bm, C, D, *, chunk, init_state):
        return ref.ssd_scan(x, dt, A, Bm, C, D, init_state=init_state, return_state=True)

    monkeypatch.setattr(ops, "ssd_segments", sequential)
    want_tokens, want_logits = run()
    assert np.array_equal(tokens, want_tokens)
    assert float(jnp.abs(logits - want_logits).max()) < 1e-4 * float(jnp.abs(want_logits).max())
