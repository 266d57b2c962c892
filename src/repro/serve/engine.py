"""Minimal batched serving engine: prefill once, decode greedily/with
temperature, jit-compiled step functions, cache reuse across requests.

``generate`` marks its host work with profiler spans (``serve.init_caches``,
``serve.prefill``, and per token ``serve.sample``, ``serve.host_read``,
``serve.decode``), which record only while a profiler runs, on the clock of
the device trace. ``serve.init_caches`` carries the bytes it allocates for
each kind of cache (``kv_bytes``, ``ssm_state_bytes``, ``conv_bytes``), and
``serve.prefill`` the trips of the SSD's segment scan its prefill runs
(``ssd_segments``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.arch import ModelArch
from repro.models import lm
from repro.models.lm import ModelCfg


@dataclasses.dataclass
class GenerateResult:
    tokens: np.ndarray  # (B, prompt + generated)
    prompt_len: int
    # measured wall time per decode step (seconds, one per generated token;
    # each step materializes its sampled token, so step i's time covers the
    # device work it waited on) — the raw material for a source="serve"
    # calibration StepTrace
    step_times: tuple = ()
    # how many leading step_times entries absorbed jit compilation (1 on the
    # first generate at a given batch shape, 0 once the engine is warm).
    # Trace emitters must drop these — a compile-polluted step skews drift
    # scoring toward spurious refits
    warmup_steps: int = 0
    # wall seconds from the call until the first generated token is on the
    # host (cache setup + prefill + first sample; includes compilation on
    # the first generate at a given prompt shape)
    first_token_s: float = 0.0


class ServeEngine:
    def __init__(self, arch: ModelArch, cfg: ModelCfg, params, max_len: int = 512):
        self.arch, self.cfg, self.params = arch, cfg, params
        self.max_len = max_len
        self._prefill = jax.jit(
            functools.partial(lm.prefill, arch=arch, cfg=cfg),
            static_argnames=(),
        )
        self._decode = jax.jit(
            functools.partial(lm.decode_step, arch=arch, cfg=cfg)
        )
        # batch sizes whose decode step has already compiled: generate()
        # reports warmup_steps=0 for these (position is traced, so one
        # executable serves every step at a given batch shape)
        self._warm_batches: set[int] = set()

    def generate(
        self,
        prompts: np.ndarray,  # (B, S_prompt) token ids
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        seed: int = 0,
        enc_features=None,
        frontend=None,
    ) -> GenerateResult:
        t_start = time.perf_counter()
        B, S = prompts.shape
        frontend_len = frontend.shape[1] if frontend is not None else 0
        total = S + frontend_len + max_new_tokens
        if total > self.max_len:
            raise ValueError(
                f"prompt_len ({S})"
                + (f" + frontend_len ({frontend_len})" if frontend_len else "")
                + f" + max_new_tokens ({max_new_tokens}) = {total} exceeds "
                f"max_len ({self.max_len}); decode positions past the KV "
                f"cache would clobber it silently"
            )
        nbytes = lm.cache_bytes(self.arch, self.cfg, B, self.max_len)
        with TraceAnnotation("serve.init_caches", **nbytes):
            caches = lm.init_caches(
                self.arch, self.cfg, B, self.max_len,
                enc_features=enc_features, params=self.params,
            )
        segments = lm.prefill_ssd_segments(self.arch, S + frontend_len)
        with TraceAnnotation("serve.prefill", ssd_segments=segments):
            logits, caches = self._prefill(
                self.params, caches=caches, tokens=jnp.asarray(prompts),
                frontend=frontend,
            )
            last = logits[:, -1, :]
        key = jax.random.PRNGKey(seed)
        out = [np.asarray(prompts)]
        pos = S + frontend_len
        warmup = 0 if B in self._warm_batches else min(1, max_new_tokens)
        step_times = []
        first_token_s = 0.0
        for i in range(max_new_tokens):
            t0 = time.perf_counter()
            with TraceAnnotation("serve.sample"):
                if temperature > 0:
                    key, sub = jax.random.split(key)
                    nxt = jax.random.categorical(sub, last / temperature, axis=-1)
                else:
                    nxt = jnp.argmax(last, axis=-1)
                nxt = nxt[:, None].astype(jnp.int32)
            # np.asarray blocks on the sampled token — and with it on the
            # decode dispatched last iteration — so the measured interval is
            # a true per-token step time, not just dispatch latency
            with TraceAnnotation("serve.host_read"):
                out.append(np.asarray(nxt))
            if i == 0:
                first_token_s = time.perf_counter() - t_start
            with TraceAnnotation("serve.decode"):
                logits, caches = self._decode(
                    self.params, caches=caches, tokens=nxt, position=pos + i
                )
                last = logits[:, -1, :]
            step_times.append(time.perf_counter() - t0)
        if max_new_tokens > 0:
            self._warm_batches.add(B)
        return GenerateResult(
            tokens=np.concatenate(out, axis=1), prompt_len=S,
            step_times=tuple(step_times), warmup_steps=warmup,
            first_token_s=first_token_s,
        )
