"""Checkpoint manager (atomic/async/keep-k/elastic) + data pipeline resume."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import MarkovCorpus, SyntheticPipeline
from repro.train.optimizer import adamw_init


def _state():
    params = {"layer": {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)}}
    return {"params": params, "opt": adamw_init(params)}


def test_roundtrip_exact(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = _state()
    mgr.save(10, state, metadata={"data_step": 7}, blocking=True)
    restored, meta = mgr.restore(state)
    assert meta["step"] == 10 and meta["data_step"] == 7
    for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_then_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state())
    mgr.wait()
    assert mgr.latest_step() == 1


def test_keep_k_garbage_collection(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _state(), blocking=True)
    assert mgr.steps() == [3, 4]


def test_no_tmp_dirs_left_behind(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, _state(), blocking=True)
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_restore_latest_and_specific(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    s = _state()
    mgr.save(1, s, blocking=True)
    s2 = jax.tree_util.tree_map(lambda x: x + 1, s)
    mgr.save(2, s2, blocking=True)
    r2, _ = mgr.restore(s)
    np.testing.assert_array_equal(
        np.asarray(r2["params"]["layer"]["w"]), np.asarray(s2["params"]["layer"]["w"])
    )
    r1, _ = mgr.restore(s, step=1)
    np.testing.assert_array_equal(
        np.asarray(r1["params"]["layer"]["w"]), np.asarray(s["params"]["layer"]["w"])
    )


def test_elastic_restore_with_shardings(tmp_path):
    """Restore placing leaves onto explicit (single-device) shardings —
    the elastic-restart path; on a pod the same call re-shards to a new mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    mgr = CheckpointManager(str(tmp_path))
    s = _state()
    mgr.save(3, s, blocking=True)
    sh = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), s)
    restored, _ = mgr.restore(s, shardings=sh)
    leaf = restored["params"]["layer"]["w"]
    assert isinstance(leaf, jax.Array) and leaf.sharding == NamedSharding(mesh, P())


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        CheckpointManager(str(tmp_path)).restore({})


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------

def test_pipeline_deterministic_per_step():
    c = MarkovCorpus(64, seed=1)
    p1 = SyntheticPipeline(corpus=c, global_batch=4, seq_len=16)
    p2 = SyntheticPipeline(corpus=c, global_batch=4, seq_len=16)
    np.testing.assert_array_equal(p1.next_batch()["tokens"], p2.next_batch()["tokens"])
    np.testing.assert_array_equal(p1.next_batch()["tokens"], p2.next_batch()["tokens"])


def test_pipeline_resume_from_state_dict():
    c = MarkovCorpus(64, seed=1)
    p = SyntheticPipeline(corpus=c, global_batch=4, seq_len=16)
    p.next_batch()
    p.next_batch()
    saved = p.state_dict()
    b3 = p.next_batch()
    q = SyntheticPipeline(corpus=c, global_batch=4, seq_len=16)
    q.load_state_dict(saved)
    np.testing.assert_array_equal(q.next_batch()["tokens"], b3["tokens"])


def test_pipeline_shards_disjoint_deterministic():
    c = MarkovCorpus(64, seed=1)
    shard0 = SyntheticPipeline(corpus=c, global_batch=8, seq_len=16,
                               shard_index=0, num_shards=2)
    shard1 = SyntheticPipeline(corpus=c, global_batch=8, seq_len=16,
                               shard_index=1, num_shards=2)
    b0, b1 = shard0.next_batch()["tokens"], shard1.next_batch()["tokens"]
    assert b0.shape == (4, 16) and b1.shape == (4, 16)
    assert not np.array_equal(b0, b1)


def test_markov_entropy_below_uniform():
    c = MarkovCorpus(64, seed=0, temperature=0.3)
    assert c.entropy_rate() < np.log(64) * 0.85


def test_markov_chain_matches_dense_oracle():
    """The (vocab, FANOUT) tables describe the same chain as the dense
    transition matrix they stand for: same entropy rate, and every sampled
    transition has positive probability there."""
    V = 4 * MarkovCorpus.FANOUT
    c = MarkovCorpus(V, seed=0)
    assert c.succ.shape == (V, MarkovCorpus.FANOUT)
    dense = np.zeros((V, V))
    np.add.at(dense, (np.arange(V)[:, None], c.succ), c.P)
    np.testing.assert_allclose(dense.sum(axis=1), 1.0)
    pi = np.full(V, 1.0 / V)
    for _ in range(2000):
        pi = pi @ dense
    h = -(dense * np.log(np.where(dense > 0, dense, 1.0))).sum(axis=1)
    np.testing.assert_allclose(c.entropy_rate(), float(pi @ h), rtol=1e-6)
    toks = c.sample(np.random.default_rng(0), 16, 32)
    assert (dense[toks[:, :-1], toks[:, 1:]] > 0).all()


def test_markov_corpus_at_a_32k_vocab_stays_small():
    c = MarkovCorpus(32000, seed=0)
    assert c.P.nbytes + c.succ.nbytes + c._cum.nbytes < 64 * 2**20
    toks = c.sample(np.random.default_rng(0), 4, 64)
    assert toks.min() >= 0 and toks.max() < 32000
    assert 0 < c.entropy_rate() < np.log(64)
