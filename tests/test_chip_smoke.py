"""CPU rehearsal of chip_smoke.py: every phase end to end at reduced sizes
(kernels in interpret mode), and the script's refusal to run off a TPU."""
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.calibration.fit import train_eta_model  # noqa: E402
from repro.configs import get_reduced  # noqa: E402


@pytest.fixture
def run_dirs(tmp_path, monkeypatch):
    """The run's own artifacts directory, seeded with a small eta model so
    the search phase loads instead of training the full one; and a compile
    cache directory of the test's own, which leaves JAX's configuration
    untouched."""
    artifacts = tmp_path / "artifacts"
    artifacts.mkdir()
    model, _ = train_eta_model(n_samples=300, n_estimators=10)
    model.save(str(artifacts / "eta_model.json"))
    monkeypatch.setenv("REPRO_ARTIFACTS", str(artifacts))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    return artifacts


def test_phases_end_to_end_on_cpu(run_dirs):
    arch = get_reduced("yi-6b")
    search = chip_smoke.phase_search(arch, 4, 32)
    assert search["eta_model"] == "loaded" and search["predicted_step_s"] > 0

    res = chip_smoke.phase_train(arch, 4, 32, 6)
    assert res["steps"] == 6 and res["losses"][-1] < res["losses"][0]
    assert res["compile_s"] > 0 and res["median_step_s"] > 0

    serve = chip_smoke.phase_serve(get_reduced("qwen3-8b"), 2, 16, 8)
    assert serve["warmup_steps"] == (1, 0)
    assert serve["ref_gap"] <= chip_smoke.SERVE_GAP_TOL

    kernels = chip_smoke.phase_kernels(
        dict(B=1, Hq=4, Hkv=2, S=128, D=64), (64, 128),
        dict(B=1, S=128, H=2, P=16, N=8), interpret=True)
    assert set(kernels) == {"flash_attention", "rmsnorm", "ssd"}


def test_main_refuses_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_script_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
