"""Serve mode whose check also compares the program's own logits with the
reference's: ``serve.py``'s window, timing, per-layer context and
``logit_gap``, for a model whose greedy tokens its layers barely move.

Under ``weights.py``'s law the tied embedding of Granite-4.0-H, multiplied
by 12, carries most of the residual stream, so each position's own token
leads its logits by about 4 where the others spread by about 0.13: greedy
decoding repeats the last prompt token whatever the layers compute, and
``logit_gap`` reads 0 for the program and for the fp8 control alike. The
logits themselves still carry the layers' work, so the check adds

  logit_rms   per checked request, over its counted positions, the norm of
              the program's logits less the reference's, each centred over
              the vocabulary at its position (a shift of every logit changes
              no output), over the norm of the reference's centred logits;
              the largest request's

The program's logits come from the engine's compiled prefill and decode
step, run once more after the window and fed the served tokens, over a
batch of the window's shape: the checked requests, and in the other rows
further requests of the window.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm

from chipbench import count
from chipbench import trace as tr
from chipbench.check import judge
from chipbench.modes import serve as S
from chipbench.modes.train import memory_peak
from chipbench.reference.common import Products, served_gaps


def _batch_rows(win: dict, sample: dict, batch: int, width: int) -> np.ndarray:
    """The checked sequences, then further sequences of the window up to a
    whole batch: (batch, width) tokens."""
    rows = list(sample["seqs"])
    for tokens, _ in win["batches"]:
        for seq in tokens[:, :width]:
            if len(rows) == batch:
                return np.stack(rows)
            if not any(np.array_equal(seq, r) for r in sample["seqs"]):
                rows.append(seq)
    return np.stack(rows)


def program_logits(server, win: dict, sample: dict, decode=None) -> np.ndarray:
    """The program's logits at the checked requests' served positions,
    (k, n, V) float32: the prefill's last position, then a decode step fed
    each served token but the last. ``decode`` stands in for the engine's
    compiled step (a planted fault)."""
    eng, k = server.engine, len(sample["seqs"])
    decode = decode or eng._decode
    P, n = server.prompt_len, sample["served"].shape[1]
    rows = _batch_rows(win, sample, server.cell.traffic["batch"], P + n - 1)
    caches = lm.init_caches(eng.arch, eng.cfg, rows.shape[0], eng.max_len, params=eng.params)
    logits, caches = eng._prefill(eng.params, caches=caches, tokens=jnp.asarray(rows[:, :P]))
    out = [logits[:k, -1]]
    for i in range(n - 1):
        logits, caches = decode(eng.params, caches=caches,
                                tokens=jnp.asarray(rows[:, P + i:P + i + 1]), position=P + i)
        out.append(logits[:k, -1])
    return np.asarray(jax.device_get(jnp.stack(out, axis=1)), np.float32)


def logit_rms(logits, ref_logits, lens) -> float:
    """``logit_rms`` of ``logits`` (k, n, V) against the reference's."""
    worst = 0.0
    for lg, ref, m in zip(logits, np.asarray(jax.device_get(ref_logits)), lens):
        lg, ref = (np.asarray(x[:m], np.float64) for x in (lg, ref))
        ref = ref - ref.mean(axis=-1, keepdims=True)
        err = lg - lg.mean(axis=-1, keepdims=True) - ref
        worst = max(worst, float(np.sqrt(np.sum(err ** 2) / np.sum(ref ** 2))))
    return worst


def numbers(ref_logits, sample: dict, logits) -> dict:
    """The compared numbers of one checked sample."""
    gap = float(np.max(served_gaps(ref_logits, sample["served"], sample["lens"])))
    return {"logit_gap": gap, "logit_rms": logit_rms(logits, ref_logits, sample["lens"])}


def run(cell) -> dict:
    t = cell.traffic
    server = S.Server(cell)
    setup_s = time.perf_counter() - cell.t0
    win = server.window(seconds=cell.seconds)
    a = dataclasses.asdict(cell.arch)
    P = server.prompt_len
    # (FLOPs, bytes) of each decode step a batch's token gaps hold, by position
    ctx = {"window": win, "decode_work": [
        count.decode_step_work(a, t["batch"], P + i) for i in range(server.max_new - 1)]}
    t_trace = time.perf_counter()
    if cell.trace:
        ctx["trace"] = tr.capture(lambda: server.window(batches=1), S.SPANS)
    peak = memory_peak(cell.devices)
    sample = server.check_sample(win, t["checked_requests"])
    t_check = time.perf_counter()
    logits = program_logits(server, win, sample)
    dtype = server.engine.cfg.dtype
    server.free()
    t_ref = time.perf_counter()
    ref = S.reference_gaps(cell, sample, Products(), dtype)
    correct, checked = judge(numbers(ref, sample, logits), cell.limits)
    timing = {"setup_s": setup_s, "window_s": win["seconds"], "trace_s": t_check - t_trace,
              "check_s": t_ref - t_check, "reference_s": time.perf_counter() - t_ref}
    n_req = len(win["batches"]) * t["batch"]
    return {
        "correct": correct, "attempted": n_req, "failed": 0,
        "e2e": {"serve_tokens_per_s": win["useful"] / win["seconds"],
                "tpot_p95_ms": float(np.percentile(win["gaps"], 95)) * 1e3,
                "setup_s": setup_s},
        "memory_peak_bytes": peak, "check": checked, "ctx": ctx, "timing": timing,
    }
