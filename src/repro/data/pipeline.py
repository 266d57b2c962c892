"""Synthetic LM data with learnable structure + a resumable pipeline.

``MarkovCorpus`` samples token streams from a fixed random first-order
Markov chain — entropy strictly below uniform, so a training run shows a
real, monotone loss descent toward the chain's entropy rate (used by the
end-to-end example and the loss-decreases test).

``SyntheticPipeline`` is the production-shaped wrapper: deterministic
per-(step, host_shard) batches so (a) every data-parallel host reads only
its shard, and (b) exact resume after checkpoint restore is a matter of
restoring one integer (no file offsets).
"""
from __future__ import annotations

import dataclasses

import numpy as np
from jax.profiler import TraceAnnotation


class MarkovCorpus:
    """First-order Markov chain over ``vocab`` states with temperature
    controlling how predictable transitions are (lower => lower entropy).

    Each state moves to one of ``FANOUT`` successors — the state plus one of
    ``FANOUT`` distinct random offsets — with its own random logits. The
    chain is held as (vocab, FANOUT) tables: a dense vocab x vocab matrix
    would take 8 GB per copy at a 32k vocabulary."""

    FANOUT = 64

    def __init__(self, vocab: int, seed: int = 0, temperature: float = 0.3):
        rng = np.random.default_rng(seed)
        k = min(self.FANOUT, vocab)
        offsets = rng.choice(vocab, size=k, replace=False)
        self.succ = (np.arange(vocab)[:, None] + offsets[None, :]) % vocab  # (V, k)
        logits = rng.normal(size=(vocab, k)) / max(temperature, 1e-3)
        p = np.exp(logits - logits.max(axis=1, keepdims=True))
        self.P = p / p.sum(axis=1, keepdims=True)  # (V, k), over succ
        self.vocab = vocab
        self._cum = np.cumsum(self.P, axis=1)
        self._cum[:, -1] = 1.0  # rounding must not leave u above every bucket

    def entropy_rate(self) -> float:
        """Nats per token of the stationary chain (loss floor)."""
        # stationary distribution via power iteration
        pi = np.full(self.vocab, 1.0 / self.vocab)
        for _ in range(200):
            nxt = np.bincount(self.succ.ravel(), weights=(pi[:, None] * self.P).ravel(),
                              minlength=self.vocab)
            done = np.abs(nxt - pi).sum() < 1e-12
            pi = nxt
            if done:
                break
        H = -(self.P * np.log(np.maximum(self.P, 1e-12))).sum(axis=1)
        return float((pi * H).sum())

    def sample(self, rng: np.random.Generator, batch: int, seq: int) -> np.ndarray:
        out = np.empty((batch, seq), dtype=np.int32)
        state = rng.integers(0, self.vocab, size=batch)
        out[:, 0] = state
        for t in range(1, seq):
            u = rng.random(batch)
            pick = (self._cum[state] > u[:, None]).argmax(axis=1)
            state = self.succ[state, pick]
            out[:, t] = state
        return out


@dataclasses.dataclass
class SyntheticPipeline:
    """Deterministic, shardable, resumable batch source."""

    corpus: MarkovCorpus
    global_batch: int
    seq_len: int
    shard_index: int = 0
    num_shards: int = 1
    step: int = 0  # checkpointable cursor

    @property
    def shard_batch(self) -> int:
        assert self.global_batch % self.num_shards == 0
        return self.global_batch // self.num_shards

    def next_batch(self) -> dict:
        """Tokens for this host's shard at the current step (advances cursor);
        a ``data.next_batch`` span in a profile."""
        with TraceAnnotation("data.next_batch"):
            rng = np.random.default_rng(
                (self.step * 1_000_003 + self.shard_index) & 0x7FFFFFFF
            )
            tokens = self.corpus.sample(rng, self.shard_batch, self.seq_len)
        self.step += 1
        return {"tokens": tokens}

    def state_dict(self) -> dict:
        return {"step": self.step}

    def load_state_dict(self, d: dict) -> None:
        self.step = int(d["step"])
