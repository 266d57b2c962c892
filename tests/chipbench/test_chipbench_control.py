"""chipbench/control.py at the program's reduced sizes: the control (the
reference with fp8 products in the program's place) reads worse than the
program on every cell, and the faults a cell can have fail its limits."""
import dataclasses
import json
import os

import pytest

from chipbench import control, run
from chipbench.check import judge

SEEDS = [2**31 + 21, 2**31 + 22]


def _cells():
    """Each cell; one on more chips than this process has CPU devices is
    marked to run where it has them (``conftest.py``)."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return [pytest.param(w["name"], marks=pytest.mark.cpu_devices(w["chips"]))
                if w["chips"] > 1 else w["name"] for w in json.load(f)["workloads"]]


@pytest.fixture(scope="module", params=_cells())
def readings(request, run_dirs):
    import jax

    if len(jax.devices()) < run.cell_files(request.param)["workload"]["chips"]:
        return None  # read in the child pytest that has the devices
    cell, files = run.make_cell(request.param, SEEDS[0], 0.0, False, rehearse=True)
    fn = control.train_readings if files["traffic"]["mode"] == "train" \
        else control.serve_readings
    return cell, fn(cell, SEEDS, SEEDS[:1])


def _numbers(cell, readings):
    """The compared numbers of a reading, without its log entries."""
    return {k: v for k, v in readings.items() if k in cell.limits}


def test_control_reads_worse_than_the_program(readings):
    cell, res = readings
    program = [_numbers(cell, v) for v in res["program"].values()]
    ctl = _numbers(cell, res["control"][SEEDS[0]])
    worst = {k: max(p[k] for p in program) for k in ctl}
    assert any(ctl[k] > 2 * worst[k] for k in ctl), (ctl, worst)


def test_control_py_reports_every_reading_that_passes(readings):
    """``control.py`` judges every control and fault reading by the cell's
    limits and names those that pass, which makes it exit 1. At these
    reduced widths the chip's limits need not hold for the control (the
    program's own readings can exceed them), so the verdicts are checked
    against ``judge`` itself and against limits that nothing passes."""
    cell, res = readings
    kinds = [k for k in ("control", "half_batch", "token") if k in res]
    passed = control._verdicts(cell, res)
    for k in kinds:
        for seed, v in res[k].items():
            assert v["correct"] == judge(v, cell.limits)[0]
            assert v["correct"] == ((k, seed) in passed)
    strict = dataclasses.replace(cell, limits={n: -1.0 for n in cell.limits})
    assert control._verdicts(strict, res) == []
    loose = dataclasses.replace(cell, limits={n: float("inf") for n in cell.limits})
    assert len(control._verdicts(loose, res)) == sum(len(res[k]) for k in kinds)


def test_faults_fail_the_limits(readings):
    cell, res = readings
    faults = [k for k in ("half_batch", "token") if k in res]
    assert faults
    for k in faults:
        ok, _ = judge(_numbers(cell, res[k][SEEDS[0]]), cell.limits)
        assert not ok, (k, res[k])
