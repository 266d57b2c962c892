"""Training substrate: optimizer, schedules, grad accumulation, convergence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.data import MarkovCorpus, SyntheticPipeline
from repro.models import lm
from repro.train.optimizer import adamw_init, adamw_update, cosine_schedule, global_norm
from repro.train.train_step import TrainStepCfg, make_train_step

CFG = lm.ModelCfg(dtype=jnp.float32, attn_impl="xla", ssm_impl="xla")


def test_adamw_converges_on_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    opt = adamw_init(params)
    for _ in range(300):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(params, grads, opt, lr=0.1, weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_grad_clipping():
    params = {"w": jnp.zeros(3)}
    opt = adamw_init(params)
    big = {"w": jnp.full(3, 1e6)}
    _, _, metrics = adamw_update(params, big, opt, lr=0.0, clip_norm=1.0)
    assert metrics["grad_norm"] > 1e6  # reported norm is pre-clip


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup_steps=10, total_steps=100, min_ratio=0.1)
    assert float(lr(jnp.array(0))) == 0.0
    assert float(lr(jnp.array(10))) == pytest.approx(1.0, rel=1e-3)
    assert float(lr(jnp.array(100))) == pytest.approx(0.1, rel=1e-2)
    assert float(lr(jnp.array(5))) == pytest.approx(0.5, rel=1e-6)


def test_global_norm():
    t = {"a": jnp.array([3.0]), "b": jnp.array([4.0])}
    assert float(global_norm(t)) == pytest.approx(5.0)


def test_grad_accumulation_matches_single_batch():
    """K-microbatch accumulated grads == one-shot grads of the mean loss.

    (Post-Adam params are NOT compared: eps-nonlinearity amplifies fp32
    summation-order noise on near-zero gradient entries.)
    """
    arch = get_reduced("yi-6b")
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, arch.vocab)}

    def loss_of(p, b):
        return lm.forward_train(p, arch, CFG, b)[0]

    g_full = jax.grad(loss_of)(params, batch)
    K = 4
    micro = jax.tree_util.tree_map(
        lambda x: x.reshape((K, x.shape[0] // K) + x.shape[1:]), batch
    )
    g_acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    for i in range(K):
        mb = jax.tree_util.tree_map(lambda x: x[i], micro)
        g = jax.grad(loss_of)(params, mb)
        g_acc = jax.tree_util.tree_map(lambda a, b: a + b / K, g_acc, g)
    rel = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-9)),
        g_full, g_acc,
    )
    assert max(jax.tree_util.tree_leaves(rel)) < 1e-4
    # and the loss metric agrees between the two train_step paths
    losses = {}
    for k in (1, 4):
        cfg = TrainStepCfg(num_microbatches=k, base_lr=1e-2, warmup_steps=0,
                           total_steps=10)
        _, _, m = make_train_step(arch, CFG, cfg)(params, adamw_init(params), batch)
        losses[k] = float(m["loss"])
    assert losses[1] == pytest.approx(losses[4], rel=1e-5)


def test_loss_decreases_toward_entropy_floor():
    arch = get_reduced("qwen3-8b")
    corpus = MarkovCorpus(arch.vocab, seed=0)
    pipe = SyntheticPipeline(corpus=corpus, global_batch=16, seq_len=64)
    cfg = TrainStepCfg(num_microbatches=1, base_lr=3e-3, warmup_steps=5,
                       total_steps=60)
    step = jax.jit(make_train_step(arch, CFG, cfg))
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    losses = []
    for _ in range(60):
        batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    floor = corpus.entropy_rate()
    assert losses[-1] < losses[0] - 1.0
    assert losses[-1] < floor + 1.5  # approaching the markov entropy rate
    assert np.isfinite(losses).all()


def test_bf16_grad_accumulation_close_to_fp32():
    arch = get_reduced("yi-6b")
    params = lm.init_params(arch, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, arch.vocab)}
    p32, _, _ = make_train_step(arch, CFG, TrainStepCfg(num_microbatches=4))(
        params, adamw_init(params), batch)
    p16, _, _ = make_train_step(
        arch, CFG, TrainStepCfg(num_microbatches=4, accum_dtype=jnp.bfloat16)
    )(params, adamw_init(params), batch)
    rel = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-9)), p32, p16
    )
    assert max(jax.tree_util.tree_leaves(rel)) < 0.05


def test_auto_strategy_fails_when_eta_model_fails(monkeypatch):
    """No fallback model: a search priced by another model would pick another
    plan, so a failure to load or train the eta model fails the run."""
    from repro.launch import train as train_mod

    def broken():
        raise RuntimeError("eta model unavailable")

    monkeypatch.setattr(train_mod, "load_or_train", broken)
    with pytest.raises(RuntimeError, match="eta model unavailable"):
        train_mod.main(["--arch", "yi-6b", "--reduced", "--auto-strategy",
                        "--steps", "1", "--batch", "4", "--seq", "32"])
