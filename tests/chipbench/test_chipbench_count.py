"""chipbench/count.py: required work from shapes, against hand counts and
against the dot FLOPs the compiler's HLO holds; the peaks table."""
import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench import count

# a reduced Yi (GQA, SwiGLU) and a reduced Mamba-2, as configuration files
# give them
YI = {"family": "dense", "num_layers": 2, "hidden": 128, "heads": 8, "kv_heads": 2,
      "head_dim": 16, "ffn": 320, "vocab": 256}
MAMBA = {"family": "ssm", "num_layers": 2, "hidden": 64, "heads": 0, "kv_heads": 0,
         "ffn": 0, "vocab": 256, "ssm_state": 16, "ssm_heads": 4, "ssm_expand": 2,
         "ssm_chunk": 32}


def test_dense_hand_count():
    # per layer: q 128x128, k and v 128x32 each, o 128x128, MLP 3x128x320
    layer = 128 * 128 + 2 * 128 * 32 + 128 * 128 + 3 * 128 * 320
    assert count.layer_matmul_params(YI) == layer
    seq = 64
    # forward per token: 2 x params of every matmul (2 layers + head), and
    # causal attention QK^T + PV over seq/2 keys: 2 x 2 x (seq/2) x 128
    fwd = 2 * (2 * layer + 128 * 256) + 2 * (2 * 2 * (seq / 2) * 128)
    assert count.train_flops_per_token(YI, seq) == pytest.approx(3 * fwd)
    assert count.train_flops_per_step(YI, 4, seq) == pytest.approx(3 * fwd * 4 * seq)


def test_ssm_hand_count():
    d, di, n, h = 64, 128, 16, 4
    layer = d * (2 * di + 2 * n + h) + di * d
    assert count.layer_matmul_params(MAMBA) == layer
    seq = 64
    # the SSD mixer adds no required product (see count.py)
    fwd = 2 * (2 * layer + d * 256)
    assert count.train_flops_per_token(MAMBA, seq) == pytest.approx(3 * fwd)


def test_decode_hand_count():
    layer = count.layer_matmul_params(YI)
    flops, nbytes = count.decode_step_work(YI, batch=4, position=9)
    # 10 positions of K and V (2 kv heads x 16) per layer, bf16
    kv = 2 * 4 * 2 * (2 * 16) * 10 * 2
    weights = (2 * layer + 128 * 256 + 5 * 128 + 4 * 128) * 2
    assert nbytes == weights + kv
    assert flops == 2 * 4 * (2 * layer + 128 * 256) + 4 * 4 * 2 * 10 * 8 * 16


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_required_flops_below_compiled_dots(family):
    """No implementation can be counted above 100 %: the required FLOPs of a
    reduced step stay under the dot FLOPs of the compiled, no-recompute
    train step (whose layer scan the accountant multiplies out)."""
    from repro.configs import get_reduced
    from repro.launch.hlo_account import account
    from repro.models.lm import ModelCfg, init_params
    from repro.train.optimizer import adamw_init
    from repro.train.train_step import TrainStepCfg, make_train_step

    arch = get_reduced("yi-6b" if family == "dense" else "mamba2-370m")
    a = dataclasses.asdict(arch)
    B, S = 2, 64
    step = make_train_step(arch, ModelCfg(dtype=jnp.float32, attn_impl="xla",
                                          ssm_impl="xla"), TrainStepCfg())
    params = jax.eval_shape(functools.partial(init_params, arch), jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw_init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    hlo = jax.jit(step).lower(params, opt, batch).compile().as_text()
    dots = account(hlo).flops
    required = count.train_flops_per_step(a, B, S)
    matmul_only = 6.0 * (a["num_layers"] * count.layer_matmul_params(a)
                         + count.head_params(a)) * B * S
    assert matmul_only <= required <= dots
    if family == "dense":
        assert required > matmul_only


def test_peaks_known_and_unknown(tmp_path):
    pk = count.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in pk["source"]
    with pytest.raises(KeyError, match="no peaks"):
        count.peaks("TPU v9 imaginary")
    path = tmp_path / "peaks.json"
    path.write_text(json.dumps({"X": {}}))
    with pytest.raises(KeyError):
        count.peaks("TPU v5 lite", str(path))
