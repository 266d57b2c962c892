"""Per-kernel allclose sweeps against the ref.py oracles, in interpret mode
(the CPU backend cannot compile Pallas; tests/test_tpu_compile.py compiles the
same kernels for a described v5e)."""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.ssd import ssd_scan_fwd
from repro.kernels.ssd_xla import ssd_chunked, ssd_segments
from repro.kernels.xla_flash import banded_flash_xla, flash_xla, flash_xla_train
from repro.models import lm


def _qkv(B, Hq, Hkv, S, T, D, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, T, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, T, D), dtype)
    return q, k, v


_TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# Pallas flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,S,T,D,causal", [
    (1, 4, 4, 128, 128, 64, True),
    (2, 8, 2, 256, 256, 64, True),     # GQA
    (1, 4, 2, 200, 200, 128, True),    # uneven blocks
    (2, 2, 1, 128, 128, 32, False),    # MQA, non-causal
    (2, 8, 2, 1, 300, 64, True),       # decode: 1 query vs long KV
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_flash_vs_oracle(B, Hq, Hkv, S, T, D, causal, dtype):
    q, k, v = _qkv(B, Hq, Hkv, S, T, D, dtype)
    out, _ = flash_attention_fwd(q, k, v, causal=causal, interpret=True)
    expected = ref.attention(q, k, v, causal=causal)
    err = jnp.abs(out.astype(jnp.float32) - expected.astype(jnp.float32)).max()
    assert float(err) < _TOL[dtype], float(err)


def test_pallas_flash_block_shape_sweep():
    q, k, v = _qkv(1, 2, 2, 256, 256, 64)
    expected = ref.attention(q, k, v, causal=True)
    for bq, bk in [(64, 64), (128, 256), (256, 128)]:
        out, _ = flash_attention_fwd(q, k, v, causal=True, block_q=bq, block_k=bk,
                                     interpret=True)
        assert float(jnp.abs(out - expected).max()) < 2e-5, (bq, bk)


def test_flash_ops_grad_matches_oracle():
    q, k, v = _qkv(1, 4, 2, 128, 128, 64)
    gp = jax.grad(lambda q: ops.flash_attention(q, k, v, impl="pallas", interpret=True).sum())(q)
    gx = jax.grad(lambda q: ops.flash_attention(q, k, v, impl="naive").sum())(q)
    assert float(jnp.abs(gp - gx).max()) < 1e-5


# ---------------------------------------------------------------------------
# XLA flash (dry-run execution path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,T,block,causal", [
    (200, 200, 64, True), (128, 128, 512, True), (100, 100, 32, False),
])
def test_xla_flash_vs_oracle(S, T, block, causal):
    q, k, v = _qkv(2, 4, 2, S, T, 32)
    out = flash_xla(q, k, v, causal=causal, block=block)
    expected = ref.attention(q, k, v, causal=causal)
    assert float(jnp.abs(out - expected).max()) < 2e-5


def test_xla_flash_cached_partial_validity():
    q, k, v = _qkv(1, 4, 2, 1, 256, 32)
    out = flash_xla(q, k, v, q_start=150, kv_valid_len=151, block=64)
    expected = ref.attention(q[:, :, :1], k[:, :, :151], v[:, :, :151], causal=False)
    assert float(jnp.abs(out - expected).max()) < 2e-5


def test_xla_flash_train_grads():
    q, k, v = _qkv(1, 4, 2, 160, 160, 32)
    f1 = lambda q, k, v: flash_xla_train(q, k, v, True, None, 64).sum()
    f2 = lambda q, k, v: ref.attention(q, k, v, causal=True).sum()
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 5e-5


def test_banded_flash_vs_banded_oracle():
    from repro.models.layers import _sliding_attention

    q, k, v = _qkv(2, 4, 2, 200, 200, 32)
    out = banded_flash_xla(q, k, v, window=32, block_q=64)
    expected = _sliding_attention(q, k, v, 32)
    assert float(jnp.abs(out - expected).max()) < 2e-5
    g1 = jax.grad(lambda q: banded_flash_xla(q, k, v, window=32, block_q=64).sum())(q)
    g2 = jax.grad(lambda q: _sliding_attention(q, k, v, 32).sum())(q)
    assert float(jnp.abs(g1 - g2).max()) < 5e-5


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64), (3, 130, 384), (1, 7, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_vs_oracle(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), dtype)
    out = rmsnorm_fwd(x, w, block_rows=64, interpret=True)
    expected = ref.rmsnorm(x, w)
    err = jnp.abs(out.astype(jnp.float32) - expected.astype(jnp.float32)).max()
    assert float(err) < _TOL[dtype]


def test_rmsnorm_grad():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 96))
    w = jnp.ones((96,))
    g1 = jax.grad(lambda x: ops.fused_rmsnorm(x, w, impl="pallas", interpret=True).sum())(x)
    g2 = jax.grad(lambda x: ref.rmsnorm(x, w).sum())(x)
    assert float(jnp.abs(g1 - g2).max()) < 1e-6


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

def _ssd_inputs(B, S, H, P, N, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    C = jax.random.normal(ks[4], (B, S, N), dtype)
    D = jax.random.normal(ks[5], (H,))
    return x, dt, A, Bm, C, D


_SSD_SHAPES = [
    (1, 128, 2, 32, 16, 64),
    (2, 300, 4, 64, 32, 128),   # S not a multiple of the chunk
    (1, 64, 1, 16, 8, 256),     # chunk > S
]


@pytest.mark.parametrize("B,S,H,P,N,chunk", _SSD_SHAPES)
def test_ssd_kernel_vs_oracle(B, S, H, P, N, chunk):
    x, dt, A, Bm, C, D = _ssd_inputs(B, S, H, P, N)
    y, state = ssd_scan_fwd(x, dt, A, Bm, C, D, chunk=chunk, interpret=True)
    ye, se = ref.ssd_scan(x, dt, A, Bm, C, D, return_state=True)
    assert float(jnp.abs(y - ye).max()) < 2e-3
    assert float(jnp.abs(state - se).max()) < 2e-3


def test_ssd_kernel_keeps_precision_under_strong_decay():
    """A chunk whose cumulative log-decay reaches hundreds: decays over a
    span must not be the difference of two such sums, which keeps only
    eps * |sum| of absolute precision."""
    x, dt, _, Bm, C, D = _ssd_inputs(1, 256, 2, 16, 8)
    dt = dt + 2.0
    A = jnp.array([-4.0, -0.5])
    y, state = ssd_scan_fwd(x, dt, A, Bm, C, D, interpret=True)
    ye, se = ref.ssd_scan(x, dt, A, Bm, C, D, return_state=True)
    assert float(jnp.abs(y - ye).max() / jnp.abs(ye).max()) < 1e-6
    assert float(jnp.abs(state - se).max() / jnp.abs(se).max()) < 1e-6


def test_ssd_streaming_equals_full():
    """Chunked decode (carrying state) == one full scan."""
    x, dt, A, Bm, C, D = _ssd_inputs(1, 96, 2, 16, 8)
    full = ref.ssd_scan(x, dt, A, Bm, C, D)
    y1, st = ref.ssd_scan(x[:, :64], dt[:, :64], A, Bm[:, :64], C[:, :64], D,
                          return_state=True)
    y2 = ref.ssd_scan(x[:, 64:], dt[:, 64:], A, Bm[:, 64:], C[:, 64:], D,
                      init_state=st)
    err = jnp.abs(jnp.concatenate([y1, y2], axis=1) - full).max()
    assert float(err) < 1e-4


def test_ssd_grad_parity():
    x, dt, A, Bm, C, D = _ssd_inputs(1, 128, 2, 16, 8)
    g1 = jax.grad(lambda x: ops.ssd(x, dt, A, Bm, C, impl="pallas", interpret=True).sum())(x)
    g2 = jax.grad(lambda x: ops.ssd(x, dt, A, Bm, C, impl="xla").sum())(x)
    assert float(jnp.abs(g1 - g2).max()) < 1e-5


@pytest.mark.parametrize("B,S,H,P,N,chunk", _SSD_SHAPES)
def test_ssd_xla_vs_oracle(B, S, H, P, N, chunk):
    """The chunked XLA form (the train path of ops.ssd(impl="xla")) against
    the sequential oracle: outputs and final state."""
    x, dt, A, Bm, C, D = _ssd_inputs(B, S, H, P, N)
    y, state = ssd_chunked(x, dt, A, Bm, C, D, chunk=chunk)
    ye, se = ref.ssd_scan(x, dt, A, Bm, C, D, return_state=True)
    assert float(jnp.abs(y - ye).max()) < 2e-3
    assert float(jnp.abs(state - se).max()) < 2e-3
    assert jnp.array_equal(ops.ssd(x, dt, A, Bm, C, D, impl="xla", chunk=chunk), y)


@pytest.mark.parametrize("form", [ssd_chunked, ssd_segments], ids=lambda f: f.__name__)
@pytest.mark.parametrize("B,S,H,P,N,chunk", _SSD_SHAPES + [(2, 97, 2, 16, 8, 32)])
def test_ssd_xla_carries_state_vs_oracle(B, S, H, P, N, chunk, form):
    """The chunked form and the prefill's scan over segments, each from a
    non-zero state, against the oracle carrying that state: outputs and
    final state (S = 97 is prime, so no chunk divides it). The steps are
    shortened so that the state carried in still counts at the end. A zero
    state is no state, bit for bit."""
    x, dt, A, Bm, C, D = _ssd_inputs(B, S, H, P, N)
    dt = dt / S
    h0 = jax.random.normal(jax.random.PRNGKey(9), (B, H, P, N))
    y, state = form(x, dt, A, Bm, C, D, chunk=chunk, init_state=h0)
    ye, se = ref.ssd_scan(x, dt, A, Bm, C, D, init_state=h0, return_state=True)
    assert float(jnp.abs(y - ye).max()) < 2e-3
    assert float(jnp.abs(state - se).max()) < 2e-3
    zero = form(x, dt, A, Bm, C, D, chunk=chunk, init_state=jnp.zeros_like(h0))
    none = form(x, dt, A, Bm, C, D, chunk=chunk)
    assert all(jnp.array_equal(a, b) for a, b in zip(zero, none))


@pytest.mark.parametrize("B,S,H,P,N,chunk", _SSD_SHAPES)
def test_ssd_xla_grads_vs_oracle(B, S, H, P, N, chunk):
    """Autodiff through the chunked form against the oracle's VJP, for all
    six inputs, each within 1e-5 of its largest entry."""
    args = _ssd_inputs(B, S, H, P, N)
    w = jax.random.normal(jax.random.PRNGKey(7), (B, S, H, P))
    _, vjp = jax.vjp(lambda *a: ref.ssd_scan(*a), *args)
    expect = vjp(w)
    got = jax.grad(lambda *a: jnp.sum(ops.ssd(*a, impl="xla", chunk=chunk) * w),
                   argnums=tuple(range(6)))(*args)
    for name, g, e in zip(("x", "dt", "A", "B", "C", "D"), got, expect):
        assert float(jnp.abs(g - e).max() / jnp.abs(e).max()) < 1e-5, name


def test_ssd_xla_keeps_precision_under_strong_decay():
    """As for the kernel: a chunk whose cumulative log-decay reaches
    hundreds, where decays over a span must be summed over the span."""
    x, dt, _, Bm, C, D = _ssd_inputs(1, 256, 2, 16, 8)
    dt = dt + 2.0
    A = jnp.array([-4.0, -0.5])
    y, state = ssd_chunked(x, dt, A, Bm, C, D)
    ye, se = ref.ssd_scan(x, dt, A, Bm, C, D, return_state=True)
    assert float(jnp.abs(y - ye).max() / jnp.abs(ye).max()) < 1e-6
    assert float(jnp.abs(state - se).max() / jnp.abs(se).max()) < 1e-6
    # the prefill's form: four segments, from the state the first half left
    half = [v[:, :128] for v in (x, dt)] + [A] + [v[:, :128] for v in (Bm, C)] + [D]
    _, h0 = ref.ssd_scan(*half, return_state=True)
    rest = [v[:, 128:] for v in (x, dt)] + [A] + [v[:, 128:] for v in (Bm, C)] + [D]
    y, state = ssd_segments(*rest, chunk=32, init_state=h0)
    assert float(jnp.abs(y - ye[:, 128:]).max() / jnp.abs(ye[:, 128:]).max()) < 1e-6
    assert float(jnp.abs(state - se).max() / jnp.abs(se).max()) < 1e-6


def _trip_counts(compiled_text: str) -> list:
    """The known trip count of every ``while`` of a compiled CPU program."""
    loops = [line for line in compiled_text.splitlines() if re.search(r"\bwhile\(", line)]
    trips = [re.search(r'"known_trip_count":\{"n":"(\d+)"\}', line) for line in loops]
    assert all(trips), loops
    return [int(t.group(1)) for t in trips]


def test_ssd_xla_train_step_has_no_per_token_loop():
    """The jitted training form, forward and backward, at S = 2048 compiles
    to no while loop that runs once per position (the oracle's scan does)."""
    S = 2048
    args = _ssd_inputs(1, S, 2, 16, 8)
    step = jax.jit(jax.grad(lambda *a: ops.ssd(*a, impl="xla").sum(), argnums=tuple(range(6))))
    assert S not in _trip_counts(step.lower(*args).compile().as_text())


def test_serve_prefill_has_no_per_token_loop():
    """The serve prefill of an interleaved model (state carried in and out)
    at a prompt of 64 chunks compiles to a scan over its 64 segments and no
    loop that runs once per position; the decode step runs no such scan."""
    arch = get_reduced("granite-4.0-h-micro")
    cfg = lm.ModelCfg(dtype=jnp.float32, attn_impl="xla", ssm_impl="xla")
    S = 64 * arch.ssm_chunk
    params = jax.eval_shape(functools.partial(lm.init_params, arch), jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: lm.init_caches(arch, cfg, 1, S + 1))
    prefill = jax.jit(functools.partial(lm.prefill, arch=arch, cfg=cfg)).lower(
        params, caches=caches, tokens=jax.ShapeDtypeStruct((1, S), jnp.int32))
    trips = _trip_counts(prefill.compile().as_text())
    assert S not in trips and 64 in trips, trips
    decode = jax.jit(functools.partial(lm.decode_step, arch=arch, cfg=cfg)).lower(
        params, caches=caches, tokens=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        position=jax.ShapeDtypeStruct((), jnp.int32))
    assert 64 not in _trip_counts(decode.compile().as_text())


@pytest.mark.parametrize("call", ["flash_attention", "rmsnorm", "ssd"])
def test_interpret_refused_off_the_cpu(monkeypatch, call):
    """On an accelerator a kernel compiles natively: asking it to interpret
    there is an error, never a silent emulation."""
    import repro.kernels as kernels

    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "tpu")
    # the check runs when the jitted kernel is traced: drop traces that
    # earlier CPU tests made at these shapes
    jax.clear_caches()
    with pytest.raises(ValueError, match="CPU backend only"):
        if call == "flash_attention":
            flash_attention_fwd(*_qkv(1, 2, 2, 128, 128, 64), interpret=True)
        elif call == "rmsnorm":
            rmsnorm_fwd(jnp.ones((8, 128)), jnp.ones((128,)), interpret=True)
        else:
            ssd_scan_fwd(*_ssd_inputs(1, 128, 2, 16, 8), interpret=True)
