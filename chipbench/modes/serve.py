"""Serve mode: ``ServeEngine.generate`` on back-to-back static batches, for a
window of seconds.

Set-up: the served weights, in the configuration's dtype, made on the
device from the seed in one call (``weights.py``, the program's layout); a
``ServeEngine`` whose ``max_len`` holds the prompt and the longest answer,
so one compiled prefill and one compiled decode step serve every batch;
one short ``generate`` at the window's batch and prompt shape to compile
both.

Traffic: each batch holds ``batch`` requests with ``prompt_len`` prompt
tokens drawn uniformly from the vocabulary. Answer lengths follow a
lognormal law (``median``, ``sigma``) clipped to [``min``, ``max``], taken at
the batch's evenly spaced quantiles, so every batch and every seed asks
for the same work; the seed draws the prompts and which request gets which
length. A batch decodes to its longest request; each request counts only
its own length as useful tokens.

The window starts batches until ``seconds`` have passed and ends with the
last one started. With a trace, one more batch runs under the profiler.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm import ModelCfg, init_params
from repro.serve import ServeEngine

from chipbench import count
from chipbench import trace as tr
from chipbench import weights as W
from chipbench.check import judge
from chipbench.modes.train import memory_peak
from chipbench.reference.common import Products, decode_logits, served_gaps

SPANS = ("batch", "prefill", "decode")


def answer_lengths(t: dict) -> list[int]:
    """The batch's answer lengths: the law's quantiles (i + 1/2) / batch."""
    o, n = t["out_len"], t["batch"]
    z = [statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return [int(min(max(round(o["median"] * math.exp(o["sigma"] * zi)), o["min"]), o["max"]))
            for zi in z]


class Server:
    """The engine with its weights, and the seeded request stream."""

    def __init__(self, cell):
        self.cell = cell
        t, arch = cell.traffic, cell.arch
        self.lens = answer_lengths(t)
        self.prompt_len = t["prompt_len"]
        self.max_new = max(self.lens)
        cfg = ModelCfg(dtype=jnp.dtype(cell.config["dtype"]), attn_impl="xla", ssm_impl="xla")
        self.struct = jax.eval_shape(functools.partial(init_params, arch, dtype=cfg.dtype),
                                     jax.random.PRNGKey(0))
        self.seed = cell.seed
        params = W.make(self.struct, cell.seed, arch.num_layers)
        self.engine = ServeEngine(arch, cfg, params, max_len=self.prompt_len + self.max_new)
        sp = cell.spans
        for name in ("_prefill", "_decode"):
            fn = getattr(self.engine, name)
            setattr(self.engine, name, _spanned(sp, name[1:], fn))
        self.rng = np.random.default_rng(cell.seed % (1 << 64))
        warm = np.zeros((t["batch"], self.prompt_len), np.int32)
        self.engine.generate(warm, max_new_tokens=2)

    def reset(self, seed: int) -> None:
        self.engine.params = None
        gc.collect()
        self.seed = seed
        self.engine.params = W.make(self.struct, seed, self.cell.arch.num_layers)
        self.rng = np.random.default_rng(seed % (1 << 64))

    def next_batch(self):
        with self.cell.spans.span("batch"):
            t = self.cell.traffic
            prompts = self.rng.integers(0, self.cell.arch.vocab,
                                        (t["batch"], self.prompt_len), dtype=np.int32)
            lens = self.rng.permutation(self.lens)
        return prompts, lens

    def window(self, seconds: float = None, batches: int = None) -> dict:
        done, gaps, useful = [], [], 0
        with self.cell.spans.span(tr.WINDOW):
            t0 = time.perf_counter()
            while True:
                prompts, lens = self.next_batch()
                res = self.engine.generate(prompts, max_new_tokens=self.max_new)
                # step_times[0] ends with the first token, so it holds the
                # prefill: the gaps between output tokens are the rest
                gaps.extend(res.step_times[max(res.warmup_steps, 1):])
                useful += int(np.sum(lens))
                done.append((res.tokens, lens))
                now = time.perf_counter()
                if (batches is not None and len(done) >= batches) or \
                        (batches is None and now - t0 >= seconds):
                    break
            # the last decode step, whose logits no token needs, is still
            # running: let it end inside the window's span
            drain(self.cell.devices)
        return {"batches": done, "gaps": gaps, "useful": useful,
                "seconds": now - t0, "t0": t0, "t1": now}

    def check_sample(self, win: dict, k: int) -> dict:
        """``k`` finished requests drawn from the seed, a longest among them:
        their prompt-and-answer sequences, served tokens and lengths, and the
        seed of the weights that served them."""
        rng = np.random.default_rng([self.seed % (1 << 64), 1])
        pool = [(b, r) for b in range(len(win["batches"])) for r in range(len(self.lens))]
        order = [pool[i] for i in rng.permutation(len(pool))]
        longest = next(p for p in order
                       if win["batches"][p[0]][1][p[1]] == self.max_new)
        pick = [longest] + [p for p in order if p != longest][:k - 1]
        P = self.prompt_len
        seqs = np.stack([win["batches"][b][0][r, :P + self.max_new - 1] for b, r in pick])
        served = np.stack([win["batches"][b][0][r, P:P + self.max_new] for b, r in pick])
        lens = np.array([win["batches"][b][1][r] for b, r in pick])
        return {"seqs": seqs, "served": served, "lens": lens, "seed": self.seed}

    def free(self) -> None:
        self.engine = None
        gc.collect()


def drain(devices) -> None:
    """Wait until every program dispatched to ``devices`` has ended: a
    device runs its programs in order, so a last small one ending says so."""
    for d in devices:
        jnp.zeros((), device=d).block_until_ready()


def _spanned(sp, name, fn):
    def call(*args, **kwargs):
        with sp.span(name):
            return fn(*args, **kwargs)
    return call


def reference_gaps(cell, sample: dict, products: Products, dtype) -> np.ndarray:
    fam = importlib.import_module("chipbench.reference." + cell.arch.family)
    P = cell.traffic["prompt_len"]
    logits = decode_logits(fam, dataclasses.asdict(cell.arch), sample["seed"],
                           sample["seqs"], P - 1, sample["served"].shape[1],
                           products, dtype=dtype)
    return logits


def run(cell) -> dict:
    t = cell.traffic
    server = Server(cell)
    setup_s = time.perf_counter() - cell.t0
    win = server.window(seconds=cell.seconds)
    a = dataclasses.asdict(cell.arch)
    P = server.prompt_len
    # (FLOPs, bytes) of each decode step a batch's token gaps hold, by position
    ctx = {"window": win, "decode_work": [
        count.decode_step_work(a, t["batch"], P + i) for i in range(server.max_new - 1)]}
    t_trace = time.perf_counter()
    if cell.trace:
        ctx["trace"] = tr.capture(lambda: server.window(batches=1), SPANS)
    peak = memory_peak(cell.devices)
    sample = server.check_sample(win, t["checked_requests"])
    dtype = server.engine.cfg.dtype
    server.free()
    t_ref = time.perf_counter()
    ref = reference_gaps(cell, sample, Products(), dtype)
    gap = float(np.max(served_gaps(ref, sample["served"], sample["lens"])))
    correct, checked = judge({"logit_gap": gap}, cell.limits)
    timing = {"setup_s": setup_s, "window_s": win["seconds"],
              "trace_s": t_ref - t_trace, "reference_s": time.perf_counter() - t_ref}
    n_req = len(win["batches"]) * t["batch"]
    return {
        "correct": correct, "attempted": n_req, "failed": 0,
        "e2e": {"serve_tokens_per_s": win["useful"] / win["seconds"],
                "tpot_p95_ms": float(np.percentile(win["gaps"], 95)) * 1e3,
                "setup_s": setup_s},
        "memory_peak_bytes": peak, "check": checked, "ctx": ctx, "timing": timing,
    }
