"""CPU rehearsal of every cell: each mode's window driver end to end at the
program's reduced sizes through the same configuration, traffic and limits
files, and the result line's schema; the check coming out false when the
timed path is broken underneath; and the runner's refusal to run off a TPU."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from chipbench import run

ROOT = run.ROOT
SEED = 2**31 + 12345  # more than 32 signed bits hold


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells(mode=None):
    out = []
    for w in _bench()["workloads"]:
        with open(os.path.join(ROOT, "chipbench", "traffic", w["traffic"] + ".json")) as f:
            if mode is None or json.load(f)["mode"] == mode:
                out.append(w["name"])
    return out


def _rehearse(workload, trace=False, seed=SEED):
    return run.execute(workload, seed, 0.3, trace, rehearse=True)


def _assert_schema(res, workload, trace):
    files = run.cell_files(workload)
    assert list(res)[-1] == "check" and res["check"]
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert res["attempted"] > 0 and res["failed"] == 0
    dev = res["device"]
    assert set(dev) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert dev["count"] == files["workload"]["chips"]
    for name, v in res["check"].items():
        assert set(v) == {"value", "limit"} and v["limit"] == files["limits"][name]
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(res["breakdown"]["device_ops"]) <= 10
        declared = {m["name"]: m["unit"] for m in files["per_layer"]}
        assert set(res["metrics"]) <= set(declared)
        for name, v in res["metrics"].items():
            assert v["unit"] == declared[name] and v["value"] == v["value"]
    else:
        assert {m["name"] for m in files["end_to_end"]} == set(res["metrics"])
        assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("workload", _cells())
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(run_dirs, workload, trace):
    """``correct`` is the check's verdict: every number within its limit.
    (The limits are set from readings at the cell's real size; bf16's
    rounding weighs more at these reduced widths.)"""
    res = _rehearse(workload, trace)
    _assert_schema(res, workload, trace)
    values = [v["value"] for v in res["check"].values()]
    assert all(0 <= v < 1 for v in values), res["check"]
    assert res["correct"] == all(v["value"] <= v["limit"] for v in res["check"].values())


def _unchanged(make_train_step):
    def make(*a, **k):
        step = make_train_step(*a, **k)

        def broken(params, opt, batch):
            _, _, metrics = step(params, opt, batch)
            return params, opt, metrics
        return broken
    return make


def _half_batch(make_train_step):
    def make(*a, **k):
        step = make_train_step(*a, **k)

        def broken(params, opt, batch):
            tokens = batch["tokens"]
            return step(params, opt, {"tokens": tokens[:, : tokens.shape[1] // 2]})
        return broken
    return make


@pytest.mark.parametrize("fault", [_unchanged, _half_batch], ids=["unchanged", "half_batch"])
@pytest.mark.parametrize("workload", _cells("train"))
def test_broken_train_step_is_not_correct(run_dirs, monkeypatch, workload, fault):
    from chipbench.modes import train

    monkeypatch.setattr(train, "make_train_step", fault(train.make_train_step))
    res = _rehearse(workload)
    assert res["correct"] is False, res["check"]


@pytest.mark.parametrize("workload", _cells("serve"))
def test_altered_token_is_not_correct(run_dirs, monkeypatch, workload):
    """The decode step's logits are shifted by one vocabulary id, so each
    token it produces is the neighbour of the model's best."""
    from chipbench.modes import serve

    real = serve.Server.__init__

    def init(self, cell):
        real(self, cell)
        decode = self.engine._decode

        def altered(*args, **kwargs):
            logits, caches = decode(*args, **kwargs)
            return jnp.roll(logits, 1, axis=-1), caches
        self.engine._decode = altered

    monkeypatch.setattr(serve.Server, "__init__", init)
    res = _rehearse(workload)
    assert res["correct"] is False, res["check"]


def test_runner_refuses_cpu():
    assert jax.devices()[0].platform == "cpu"
    w = _cells()[0]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
                        "--workload", w, "--seed", str(SEED), "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True, env=env,
                       cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
