"""Run the searched plan on one TPU v5e: search, train, serve, Pallas kernels.

    python chip_smoke.py             # every phase, one chip
    python chip_smoke.py --chips 4   # train phase only: 4-chip mesh vs one chip

Phases, in order (``--chips 4`` runs only the train comparison):

* search  — the paper's mode-1 search for one v5e on the training model;
  the eta model is trained from its seed in a directory this run owns.
* train   — ``repro.launch.train.main --auto-strategy --dtype bfloat16`` on
  llama2-7b at its published widths, cut from 32 to 2 layers, global batch
  4 x seq 2048; the loss must be finite and fall.
* serve   — a ``ServeEngine`` at qwen3-8b widths in bf16, cut from 36 to 16
  layers: 4 requests of 512 prompt tokens, 64 greedy new tokens each. Every
  greedy token must be (near-)argmax under a teacher-forced forward pass.
* kernels — each Pallas kernel compiled natively at these models' widths,
  against its ``kernels/ref.py`` oracle.

Each phase prints one line with its timing and key numbers; any failure
raises and exits non-zero. The last line of a passing run is the JSON
result ``{"ok": true, "device": {...}}``. There is no CPU fallback: where
JAX finds no TPU the script exits non-zero before any phase and prints no
result line. Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``artifacts/jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.calibration.fit import load_or_train  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core.arch import ModelArch  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention_fwd  # noqa: E402
from repro.kernels.rmsnorm import rmsnorm_fwd  # noqa: E402
from repro.kernels.ssd import ssd_scan_fwd  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402

# published widths, depth cut to fit one 16 GB chip
TRAIN_ARCH = dataclasses.replace(get_arch("llama2-7b"), name="llama2-7b-2l", num_layers=2)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 2048, 6
SERVE_ARCH = dataclasses.replace(get_arch("qwen3-8b"), name="qwen3-8b-16l", num_layers=16)
SERVE_REQUESTS, PROMPT_LEN, NEW_TOKENS = 4, 512, 64
# kernel shapes: llama/qwen attention (32 q heads, 8 kv heads, D=128), the
# 4096-wide hidden state of both, and mamba2-370m's SSD (H=32, P=64, N=128)
FLASH_SHAPE = dict(B=1, Hq=32, Hkv=8, S=2048, D=128)
RMSNORM_SHAPE = (8192, 4096)
SSD_SHAPE = dict(B=1, S=2048, H=32, P=64, N=128)

# tests/test_kernels.py's tolerances, by dtype, on the error beyond one
# rounding step of the output dtype (see _run_kernel)
KERNEL_TOL = {"bfloat16": 2e-2, "float32": 2e-5}
SSD_TOL = 2e-3  # f32, the chunked form against the sequential scan
# a greedy token may trail the reference's best logit by this share of the
# row's largest |logit|: bf16 noise between the cached decode path and the
# full forward, far below the gap of a wrong token (O(1) of the scale)
SERVE_GAP_TOL = 5e-2
# four chips vs one: per-step loss, relative (bf16 reductions in another order)
LOSS_REL_TOL = 1e-2


class SmokeError(AssertionError):
    """A phase produced a wrong or missing result."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_search(arch: ModelArch, global_batch: int, seq: int) -> dict:
    """Mode-1 search for one v5e; the eta model comes from ``load_or_train``
    (trained from its seed unless the artifacts directory already holds it)."""
    t0 = time.perf_counter()
    _, fit_report = load_or_train()
    eta_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    report = train.pick_strategy(arch, 1, global_batch, seq)
    search_s = time.perf_counter() - t0
    best = report.best
    _check(best is not None, "search found no feasible strategy")
    out = {
        "winner": (f"tp={best.tensor_parallel} pp={best.pipeline_parallel} "
                   f"dp={best.data_parallel} mbs={best.micro_batch_size} "
                   f"recompute={best.recompute_granularity} "
                   f"microbatches={best.num_microbatches(global_batch)}"),
        "predicted_step_s": report.best_sim.step_time,
        "eta_model": "trained" if fit_report is not None else "loaded",
        "eta_s": eta_s, "search_s": search_s,
    }
    print(f"[search] {arch.name} on 1 x tpu-v5e, batch {global_batch} x seq {seq}: "
          f"{out['winner']} predicted_step={out['predicted_step_s']:.4f}s | "
          f"eta model {out['eta_model']} in {eta_s:.2f}s, search {search_s:.2f}s",
          flush=True)
    return out


def phase_train(arch: ModelArch, global_batch: int, seq: int, steps: int,
                devices=None) -> dict:
    """``train.main`` with the searched plan in bf16; loss finite and falling."""
    t0 = time.perf_counter()
    res = train.main(
        ["--auto-strategy", "--dtype", "bfloat16", "--steps", str(steps),
         "--batch", str(global_batch), "--seq", str(seq), "--log-every", "1"],
        arch=arch, devices=devices,
    )
    wall_s = time.perf_counter() - t0
    losses = res["losses"]
    _check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    n = len(devices) if devices is not None else jax.device_count()
    print(f"[train] {arch.name} on {n} device(s), batch {global_batch} x seq {seq}, "
          f"remat={res['remat']} microbatches={res['microbatches']}: "
          f"compile {res['compile_s']:.2f}s, median step {res['median_step_s']:.4f}s "
          f"(predicted {res['predicted_step_s']}), loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}, wall {wall_s:.2f}s", flush=True)
    return res


def phase_serve(arch: ModelArch, requests: int, prompt_len: int,
                new_tokens: int, seed: int = 0) -> dict:
    """Greedy batched generate twice (cold, then warm) and check every
    generated token against a teacher-forced forward pass."""
    cfg = lm.ModelCfg(dtype=jnp.bfloat16, attn_impl="xla", ssm_impl="xla")
    params = jax.jit(functools.partial(lm.init_params, arch, dtype=jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    engine = ServeEngine(arch, cfg, params, max_len=prompt_len + new_tokens)
    prompts = np.random.default_rng(seed).integers(
        0, arch.vocab, size=(requests, prompt_len), dtype=np.int32)
    t0 = time.perf_counter()
    cold = engine.generate(prompts, max_new_tokens=new_tokens, temperature=0.0)
    cold_s = time.perf_counter() - t0
    warm = engine.generate(prompts, max_new_tokens=new_tokens, temperature=0.0)
    tokens = warm.tokens
    _check(tokens.shape == (requests, prompt_len + new_tokens),
           f"tokens shape {tokens.shape}")
    _check(bool(((tokens >= 0) & (tokens < arch.vocab)).all()), "token out of vocab")
    _check(np.array_equal(cold.tokens, tokens), "greedy decode not deterministic")
    _check(cold.warmup_steps == 1 and warm.warmup_steps == 0,
           f"warmup_steps cold={cold.warmup_steps} warm={warm.warmup_steps}")

    @jax.jit
    def worst_gap(params, tokens):
        # logits at position t score token t+1: the generated tokens are
        # scored by positions prompt_len-1 .. end-1
        logits = lm.forward_logits(params, arch, cfg, {"tokens": tokens[:, :-1]})
        logits = logits[:, prompt_len - 1:].astype(jnp.float32)
        chosen = jnp.take_along_axis(logits, tokens[:, prompt_len:, None], axis=-1)[..., 0]
        gap = logits.max(axis=-1) - chosen
        return (gap / jnp.abs(logits).max(axis=-1)).max()

    gap = float(worst_gap(params, jnp.asarray(tokens)))
    _check(gap <= SERVE_GAP_TOL, f"greedy token {gap:.4f} of scale below the "
                                 f"reference's best (bound {SERVE_GAP_TOL})")
    decode = warm.step_times[warm.warmup_steps:]
    out = {
        "first_token_s": warm.first_token_s, "cold_first_token_s": cold.first_token_s,
        "median_decode_step_s": statistics.median(decode), "cold_generate_s": cold_s,
        "warmup_steps": (cold.warmup_steps, warm.warmup_steps), "ref_gap": gap,
    }
    print(f"[serve] {arch.name}: {requests} requests x {prompt_len} prompt + "
          f"{new_tokens} new tokens, greedy: ttft {warm.first_token_s:.4f}s "
          f"(cold {cold.first_token_s:.2f}s incl. compile), median decode step "
          f"{out['median_decode_step_s'] * 1e3:.3f}ms, warmup_steps cold/warm "
          f"{cold.warmup_steps}/{warm.warmup_steps}, worst gap to reference "
          f"{gap:.5f} (bound {SERVE_GAP_TOL}), first tokens {tokens[:, prompt_len].tolist()}",
          flush=True)
    return out


def _run_kernel(name: str, fn, args, expected_fn, tol: float, interpret: bool) -> dict:
    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    native = "tpu_custom_call" in lowered.as_text()
    _check(interpret or native, f"{name}: no tpu_custom_call in the lowering")
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    run_s = time.perf_counter() - t0
    # the oracle at full f32 matmul precision: the TPU's default would round
    # the f32 reference's products to bf16
    with jax.default_matmul_precision("highest"):
        expected = jax.jit(expected_fn)(*args)
    outs = out if isinstance(out, tuple) else (out,)
    exps = expected if isinstance(expected, tuple) else (expected,)
    err = excess = 0.0
    for o, e in zip(outs, exps):
        diff = jnp.abs(o.astype(jnp.float32) - e.astype(jnp.float32))
        # both sides round to the output dtype, and where their float32
        # values straddle a rounding boundary they differ by one step of it
        # at the value's size (0.03125 for a bf16 value in [4, 8), above the
        # tests' 2e-2 bound); only the error beyond that step counts
        step = jnp.finfo(o.dtype).eps * jnp.abs(e.astype(jnp.float32))
        err = max(err, float(diff.max()))
        excess = max(excess, float((diff - step).max()))
    _check(math.isfinite(err) and excess < tol,
           f"{name}: error beyond one rounding step {excess} >= {tol} (max |err| {err})")
    mode = "interpret" if interpret else "native"
    print(f"[kernels] {name} ({mode}): compile {compile_s:.2f}s, run {run_s * 1e3:.3f}ms, "
          f"max |err| {err:.3g}, beyond one rounding step {excess:.3g} < {tol}", flush=True)
    return {"compile_s": compile_s, "run_s": run_s, "err": err, "excess": excess}


def phase_kernels(flash_shape: dict, rmsnorm_shape: tuple, ssd_shape: dict, *,
                  interpret: bool = False, seed: int = 0) -> dict:
    """Each Pallas kernel against its ``kernels/ref.py`` oracle. ``interpret``
    is for the CPU rehearsal in the tests; the script never sets it."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    bf16, f32 = jnp.bfloat16, jnp.float32
    out = {}

    B, Hq, Hkv, S, D = (flash_shape[k] for k in ("B", "Hq", "Hkv", "S", "D"))
    q = jax.random.normal(keys[0], (B, Hq, S, D), bf16)
    k = jax.random.normal(keys[1], (B, Hkv, S, D), bf16)
    v = jax.random.normal(keys[2], (B, Hkv, S, D), bf16)
    out["flash_attention"] = _run_kernel(
        f"flash_attention {B}x{Hq}/{Hkv}x{S}x{D} bf16",
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True, interpret=interpret)[0],
        (q, k, v), lambda q, k, v: ref.attention(q, k, v, causal=True),
        KERNEL_TOL["bfloat16"], interpret)

    x = jax.random.normal(keys[3], rmsnorm_shape, bf16)
    w = jax.random.normal(keys[4], rmsnorm_shape[-1:], bf16)
    out["rmsnorm"] = _run_kernel(
        f"rmsnorm {rmsnorm_shape[0]}x{rmsnorm_shape[1]} bf16",
        lambda x, w: rmsnorm_fwd(x, w, interpret=interpret),
        (x, w), ref.rmsnorm, KERNEL_TOL["bfloat16"], interpret)

    B, S, H, P, N = (ssd_shape[k] for k in ("B", "S", "H", "P", "N"))
    ssd_args = (
        jax.random.normal(keys[5], (B, S, H, P), f32),
        jax.nn.softplus(jax.random.normal(keys[6], (B, S, H), f32)),
        -jnp.exp(jax.random.normal(keys[7], (H,), f32)),
        jax.random.normal(keys[8], (B, S, N), f32),
        jax.random.normal(jax.random.fold_in(keys[8], 1), (B, S, N), f32),
        jax.random.normal(jax.random.fold_in(keys[8], 2), (H,), f32),
    )
    out["ssd"] = _run_kernel(
        f"ssd B={B} S={S} H={H} P={P} N={N} f32",
        lambda *a: ssd_scan_fwd(*a, interpret=interpret),
        ssd_args, lambda *a: ref.ssd_scan(*a, return_state=True), SSD_TOL, interpret)
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class _CompileMeter:
    """Backend compile seconds and persistent-cache hits in this process."""

    def __init__(self):
        self.seconds, self.cache_hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def run_one_chip() -> None:
    phase_search(TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ)
    phase_train(TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, devices=jax.devices()[:1])
    phase_serve(SERVE_ARCH, SERVE_REQUESTS, PROMPT_LEN, NEW_TOKENS)
    phase_kernels(FLASH_SHAPE, RMSNORM_SHAPE, SSD_SHAPE)


def run_four_chips() -> None:
    """The same train run through the FSDP data mesh over all four chips,
    then on one of them; losses must agree and every chip hold a share."""
    devices = jax.devices()[:4]
    four = phase_train(TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, devices=devices)
    in_use = four["memory_in_use"]
    print("[train] bytes in use per device after the 4-chip run: "
          + ", ".join(f"{d.id}:{b}" for d, b in zip(devices, in_use)), flush=True)
    _check(all(b is not None and b > 0 for b in in_use), f"bytes in use {in_use}")
    _check(min(in_use) >= 0.5 * max(in_use), f"state not spread over the chips: {in_use}")
    one = phase_train(TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, devices=devices[:1])
    rel = [abs(a - b) / abs(b) for a, b in zip(four["losses"], one["losses"])]
    print(f"[train] 4-chip vs 1-chip per-step loss, max relative difference "
          f"{max(rel):.3g} (bound {LOSS_REL_TOL}): {four['losses']} vs {one['losses']}",
          flush=True)
    _check(max(rel) <= LOSS_REL_TOL, f"4-chip losses diverge from 1-chip: {rel}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the train phase, 4-chip mesh against one chip")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{devices[0].platform!r}; nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    meter = _CompileMeter()
    t0 = time.perf_counter()
    # the eta model is rebuilt from its seed here, never read from a
    # leftover artifacts/eta_model.json
    saved = os.environ.get("REPRO_ARTIFACTS")
    with tempfile.TemporaryDirectory(prefix="astra-eta-") as eta_dir:
        os.environ["REPRO_ARTIFACTS"] = eta_dir
        try:
            run_four_chips() if args.chips == 4 else run_one_chip()
        finally:
            if saved is None:
                os.environ.pop("REPRO_ARTIFACTS", None)
            else:
                os.environ["REPRO_ARTIFACTS"] = saved
    print(f"[compile] backend compile {meter.seconds:.2f}s, persistent cache hits "
          f"{meter.cache_hits} (cache {cache_dir}); total wall "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
