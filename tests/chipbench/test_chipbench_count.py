"""chipbench/count.py: required work from shapes, against hand counts and
against the dot FLOPs the compiler's HLO holds; the peaks table."""
import dataclasses
import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import count, run

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
    assert count.matmul_params(YI) == 2 * layer
    seq = 64
    # forward per token: 2 x params of every matmul (2 layers + head), and
    # causal attention QK^T + PV over seq/2 keys: 2 x 2 x (seq/2) x 128
    fwd = 2 * (2 * layer + 128 * 256) + 2 * (2 * 2 * (seq / 2) * 128)
    assert count.train_flops_per_token(YI, seq) == pytest.approx(3 * fwd)
    assert count.train_flops_per_step(YI, 4, seq) == pytest.approx(3 * fwd * 4 * seq)


def test_ssm_hand_count():
    d, di, n, h = 64, 128, 16, 4
    layer = d * (2 * di + 2 * n + h) + di * d
    assert count.matmul_params(MAMBA) == 2 * layer
    seq = 64
    # the SSD mixer adds no required product (see count.py)
    fwd = 2 * (2 * layer + d * 256)
    assert count.train_flops_per_token(MAMBA, seq) == pytest.approx(3 * fwd)


def test_decode_hand_count():
    layer = count.matmul_params(YI) // 2
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
    matmul_only = 6.0 * (count.matmul_params(a) + count.head_params(a)) * B * S
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


def test_ssm_decode_hand_count():
    """Every weight once, the embedding's looked-up rows, and per layer the
    (batch, heads, head_dim, state) float32 SSD state and the last three
    convolution inputs (bf16), each read and written; no KV cache."""
    d, di, n, h = 64, 128, 16, 4
    conv = di + 2 * n
    layer = d * (2 * di + 2 * n + h) + di * d + 4 * conv + conv + 3 * h + d
    flops, nbytes = count.decode_step_work(MAMBA, batch=4, position=9)
    weights = (2 * layer + d + d * 256 + 4 * d) * 2
    state = 2 * (4 * h * (di // h) * n * 4 + 4 * 3 * conv * 2)
    assert nbytes == weights + 2 * state
    assert flops == 2 * 4 * (count.matmul_params(MAMBA) + d * 256)
    # the state does not grow with the position
    assert count.decode_step_work(MAMBA, batch=4, position=900) == (flops, nbytes)


# --- every existing cell's counts, pinned to what they read before the
# counts moved into the family modules -------------------------------------

def _arch(name):
    with open(os.path.join(run.ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)["arch"]


# the decode cell's token gaps hold the decode steps at positions 512 .. 894
# (prompt 512, longest answer 384: the first token comes from the prefill)
DECODE_POSITIONS = range(512, 512 + 383)

PINS = {
    # config: (train batch, seq), train_flops_per_step, prefill_flops(16, 512),
    # decode_step_work(16, 512), (16, 894), sha256 of the repr of all 383
    "yi-6b": ((1, 4096), 155701154414592.0, 96104188215296.0,
              (189859364864.0, 12135833600.0), (193063813120.0, 12536389632.0),
              "96cfecf70f5dae6bd72bdb29c9dee31d2ad11b4626901070768547a2730237fc"),
    "yi-6b.l2": ((1, 4096), 15771119910912.0, 10033043603456.0,
                 (19730530304.0, 1250140160.0), (19930808320.0, 1275174912.0),
                 "aa99f339bd557d6eea4b866793f2576640493ab10e5d84a1aa094ce3789789b8"),
    "mamba2-370m": ((2, 2048), 9035134795776.0, 6023423197184.0, None, None, None),
}


def test_decode_positions_are_the_cells():
    from chipbench.modes.serve import answer_lengths

    with open(os.path.join(run.ROOT, "chipbench", "traffic", "decode.chat.json")) as f:
        t = json.load(f)
    assert DECODE_POSITIONS == range(t["prompt_len"], t["prompt_len"] + max(answer_lengths(t)) - 1)


@pytest.mark.parametrize("name", sorted(PINS))
def test_counts_pinned(name):
    (b, s), train, prefill, first, last, digest = PINS[name]
    a = _arch(name)
    assert count.train_flops_per_step(a, b, s) == train
    assert count.prefill_flops(a, 16, 512) == prefill
    if first is None:
        return
    work = [count.decode_step_work(a, 16, p) for p in DECODE_POSITIONS]
    assert work[0] == first and work[-1] == last
    assert hashlib.sha256(repr(work).encode()).hexdigest() == digest


def test_mamba2_decode_state_by_hand():
    """mamba2-370m at the decode cell's batch of 16: 48 layers of 32 heads x
    64 x 128 float32 state and 3 x 2304 bf16 convolution inputs."""
    a = _arch("mamba2-370m")
    flops, nbytes = count.decode_step_work(a, 16, 512)
    layer = 1024 * 4384 + 2048 * 1024 + 4 * 2304 + 2304 + 3 * 32 + 1024
    weights = (48 * layer + 1024 + 1024 * 50288 + 16 * 1024) * 2
    state = 48 * 2 * (16 * 32 * 64 * 128 * 4 + 16 * 3 * 2304 * 2)
    assert nbytes == weights + state
    assert flops == 2 * 16 * (48 * (1024 * 4384 + 2048 * 1024) + 1024 * 50288)


def test_yi_l8_train_work():
    """The four-chip cell's step: 8 layers of 173.0e6 matmul weights, the
    2 x 262e6 of embedding and head (only the head's enter a product), and
    causal attention over 4096 positions, for 8 rows."""
    a = _arch("yi-6b.l8")
    layer = 4096 * (4096 + 2 * 512) + 4096 * 4096 + 3 * 4096 * 11008
    per_token = 3 * (2 * (8 * layer + 4096 * 64000) + 8 * 2 * 4096 * 32 * 128)
    assert count.train_flops_per_step(a, 8, 4096) == per_token * 8 * 4096
    assert 3.4e14 < per_token * 8 * 4096 < 3.6e14
