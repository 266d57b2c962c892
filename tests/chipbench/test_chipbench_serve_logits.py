"""The ``serve_logits`` mode and ``control_logits.py`` at the program's
reduced sizes: the re-run's logits pick the tokens the window served; the
control (the reference with fp8 products in the program's place) reads
worse than the program on ``logit_rms``; and the faults a cell can have fail
its limits, among them the Mamba layers' lost recurrent state, which
``logit_gap`` alone lets pass."""
import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control_logits, run
from chipbench.check import judge

SEEDS = [2**31 + 21, 2**31 + 22]


def _cells():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    return [w["name"] for w in cells
            if run.cell_files(w["name"])["traffic"]["mode"] == "serve_logits"]


@pytest.fixture(scope="module", params=_cells())
def readings(request, run_dirs):
    cell, _ = run.make_cell(request.param, SEEDS[0], 0.0, False, rehearse=True)
    return cell, control_logits.readings(cell, SEEDS, SEEDS[:1])


def test_control_reads_worse_than_the_program(readings):
    cell, res = readings
    worst = max(v["logit_rms"] for v in res["program"].values())
    assert res["control"][SEEDS[0]]["logit_rms"] > 2 * worst, res


def test_faults_fail_the_limits(readings):
    cell, res = readings
    for kind in ("token", "state"):
        ok, _ = judge(res[kind][SEEDS[0]], cell.limits)
        assert not ok, (kind, res[kind])


def test_verdicts_name_every_reading_that_passes(readings):
    cell, res = readings
    passed = control_logits.verdicts(cell, res)
    for kind in control_logits.FAULTS:
        for seed, v in res[kind].items():
            assert v["correct"] == judge(v, cell.limits)[0] == ((kind, seed) in passed)
    strict = dataclasses.replace(cell, limits={n: -1.0 for n in cell.limits})
    assert control_logits.verdicts(strict, res) == []
    loose = dataclasses.replace(cell, limits={n: float("inf") for n in cell.limits})
    assert len(control_logits.verdicts(loose, res)) == 3


@pytest.mark.parametrize("workload", _cells())
def test_program_logits_pick_the_served_tokens(run_dirs, workload):
    """The engine's compiled steps run again over the checked requests, fed
    the served tokens, choose those tokens at every counted position."""
    from chipbench.modes import serve_logits
    from chipbench.modes.serve import Server

    cell, _ = run.make_cell(workload, SEEDS[1], 0.0, False, rehearse=True)
    server = Server(cell)
    win = server.window(batches=2)
    sample = server.check_sample(win, cell.traffic["checked_requests"])
    logits = serve_logits.program_logits(server, win, sample)
    assert logits.shape == sample["served"].shape + (cell.arch.vocab,)
    for lg, served, m in zip(logits, sample["served"], sample["lens"]):
        np.testing.assert_array_equal(lg[:m].argmax(axis=-1), served[:m])


def _planted(monkeypatch, wrap):
    """Every engine the cell builds runs ``wrap`` of its decode step."""
    from chipbench.modes import serve

    real = serve.Server.__init__

    def init(self, cell):
        real(self, cell)
        self.engine._decode = wrap(self.engine._decode)

    monkeypatch.setattr(serve.Server, "__init__", init)


@pytest.mark.parametrize("workload", _cells())
def test_lost_state_is_not_correct(run_dirs, monkeypatch, workload):
    """The decode step loses the convolution and SSD state: the tokens,
    which the embedding sets, keep ``logit_gap`` within its limit, and
    ``logit_rms`` fails."""
    _planted(monkeypatch, control_logits.state_lost)
    res = run.execute(workload, SEEDS[0], 0.3, False, rehearse=True)
    assert res["correct"] is False, res["check"]
    gap = res["check"]["logit_gap"]
    assert gap["value"] <= gap["limit"], res["check"]


@pytest.mark.parametrize("workload", _cells())
def test_altered_token_is_not_correct(run_dirs, monkeypatch, workload):
    """The decode step's logits are shifted by one vocabulary id."""
    def altered(decode):
        def step(*args, **kwargs):
            logits, caches = decode(*args, **kwargs)
            return jnp.roll(logits, 1, axis=-1), caches
        return step

    _planted(monkeypatch, altered)
    res = run.execute(workload, SEEDS[0], 0.3, False, rehearse=True)
    assert res["correct"] is False, res["check"]
