"""Shared set-up of the benchmark's CPU tests: the checkout's root and
``src`` on the path, and a small eta model in a directory of the tests'
own so a cell's search loads it instead of training the full one."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="module")
def run_dirs(tmp_path_factory):
    from repro.calibration.fit import train_eta_model

    tmp = tmp_path_factory.mktemp("chipbench")
    artifacts = tmp / "artifacts"
    artifacts.mkdir()
    model, _ = train_eta_model(n_samples=300, n_estimators=10)
    model.save(str(artifacts / "eta_model.json"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_ARTIFACTS", str(artifacts))
        mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp / "jax_cache"))
        yield tmp
