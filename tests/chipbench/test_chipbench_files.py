"""The benchmark's data files and its plain references: BENCHMARK.json
against the layout the harness reads, every configuration's arch against
its published keys, the seeded weights, and each family's float32
reference against the program's forward pass at reduced sizes."""
import dataclasses
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench import weights as W
from chipbench.reference import common, dense, ssm

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_layout(bench):
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert set(bench["paths"]) == {"chipbench", "tests/chipbench"}
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.25 for m in bench["end_to_end"])
    for entry in bench["configs"] + bench["workloads"] + bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for w in cells.values():
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, "chipbench", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(ROOT, "chipbench", "limits", w["name"] + ".json"))
        reported = [m for m in bench["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2 and any(m["name"] == "setup_s" for m in reported)
        assert any(w["name"] in m["workloads"] for m in bench["per_layer"])
        # a batch cut, which a configuration states and lists in ``reduced``,
        # is the batch run
        with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
            batch = json.load(f).get("global_batch")
        with open(os.path.join(ROOT, "chipbench", "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert batch in (None, mix.get("global_batch"))
        assert (batch is not None) == ("global_batch" in configs[w["config"]]["reduced"])
    # four chips only where the cell needs them: at most half of the cells
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    for c in configs.values():
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in cells.values())
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", m["name"] + ".py"))
        for w in m["workloads"]:
            moved = [x for x in bench["end_to_end"] if x["name"] == m["moves"]][0]
            assert w in moved.get("workloads", [w])


# published key -> ModelArch field, per source format
_HF = {"hidden_size": "hidden", "intermediate_size": "ffn", "num_hidden_layers": "num_layers",
       "num_attention_heads": "heads", "num_key_value_heads": "kv_heads",
       "vocab_size": "vocab", "tie_word_embeddings": "tie_embeddings"}
_MAMBA = {"d_model": "hidden", "n_layer": "num_layers", "tie_embeddings": "tie_embeddings"}


@pytest.mark.parametrize("name", ["yi-6b", "yi-6b.l2", "mamba2-370m", "yi-6b.l8"])
def test_config_arch_matches_its_keys(bench, name):
    conf = {c["name"]: c for c in bench["configs"]}[name]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == conf["source"]
    keys = _MAMBA if "d_model" in cfg else _HF
    for k, field in keys.items():
        assert cfg["arch"][field] == cfg[k], k
    if "d_model" in cfg:  # the embedding rows: vocab padded as mamba_ssm pads it
        m = cfg["pad_vocab_size_multiple"]
        assert cfg["arch"]["vocab"] == -(-cfg["vocab_size"] // m) * m


def test_seeded_layers_made_again_alike():
    from repro.configs import get_reduced
    from repro.models.lm import init_params

    arch = get_reduced("yi-6b")
    struct = jax.eval_shape(functools.partial(init_params, arch, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    p = W.make(struct, 2**40 + 3, arch.num_layers)
    key = W.seed_key(2**40 + 3)
    made = dict(zip(W.paths(p), jax.tree_util.tree_leaves(p)))
    for path, s in zip(W.paths(struct), jax.tree_util.tree_leaves(struct)):
        if path.startswith("layers/"):
            again = W.leaf(key, path, s.shape[1:], arch.num_layers, 1).astype(s.dtype)
            got = made[path][1]
        else:
            again = W.leaf(key, path, s.shape, arch.num_layers).astype(s.dtype)
            got = made[path]
        # the same values, but for a rare last-bit difference where the
        # compiler fuses the scaling otherwise: at most one bf16 step
        a32, g32 = (np.asarray(x, np.float32) for x in (again, got))
        step = np.abs(g32) * 2.0 ** -7 + 1e-30
        assert np.all(np.abs(a32 - g32) <= step)
        assert np.mean(a32 != g32) < 1e-3
    other = W.make(struct, 2**40 + 4, arch.num_layers)
    assert not np.array_equal(np.asarray(other["embed"]), np.asarray(p["embed"]))


@pytest.mark.parametrize("preset,fam", [("yi-6b", dense), ("mamba2-370m", ssm)])
def test_reference_matches_program_in_float32(preset, fam):
    """At float32 and reduced sizes the program's forward pass and the plain
    reference agree to rounding: they compute the same model."""
    from repro.configs import get_reduced
    from repro.models.lm import ModelCfg, forward_logits, init_params

    arch = dataclasses.replace(get_reduced(preset), tie_embeddings=preset != "yi-6b",
                               ssm_chunk=16)
    a = dataclasses.asdict(arch)
    struct = jax.eval_shape(functools.partial(init_params, arch), jax.random.PRNGKey(0))
    shapes = common.param_shapes(fam, a)
    assert sorted(W.paths(struct)) == sorted(shapes)
    for path, s in zip(W.paths(struct), jax.tree_util.tree_leaves(struct)):
        assert tuple(s.shape) == tuple(shapes[path])
    p = W.make(struct, 123, arch.num_layers)
    toks = np.random.default_rng(0).integers(0, arch.vocab, (2, 64)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog = forward_logits(p, arch, ModelCfg(dtype=jnp.float32, attn_impl="xla",
                                                ssm_impl="xla"), {"tokens": jnp.asarray(toks)})
        ref = common.decode_logits(fam, a, 123, toks, 0, 64, common.Products(),
                                   dtype=jnp.float32)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(prog - ref))) < 1e-5 * scale
    # the control's fp8 products land far from the float32 reference
    with jax.default_matmul_precision("highest"):
        ctl = common.decode_logits(fam, a, 123, toks, 0, 64, common.Products(fp8=True),
                                   dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(ctl - ref))) > 1e-2 * scale


def test_reference_adamw_follows_its_schedule():
    hp = {"base_lr": 3e-3, "warmup_steps": 10, "total_steps": 1000}
    assert common.lr_at(1, hp) == pytest.approx(3e-4)
    assert common.lr_at(10, hp) == pytest.approx(3e-3)
    assert common.lr_at(1000, hp) == pytest.approx(3e-4)
    p = {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}
    g = {"w": jnp.full((2, 2), 3.0), "b": jnp.full((2,), 4.0)}
    z = jax.tree_util.tree_map(jnp.zeros_like, p)
    new, mu, nu, gnorm = common.adamw_step(p, g, z, z, 1, 0.1)
    assert float(gnorm) == pytest.approx(np.sqrt(4 * 9 + 2 * 16))
    # first step: |delta| = 1 per element; decay only on the 2-D leaf
    np.testing.assert_allclose(np.asarray(new["b"]), 0.9, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new["w"]), 1 - 0.1 * (1 + 0.1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(mu["w"]), 0.1 * 3 / float(gnorm), rtol=1e-6)
