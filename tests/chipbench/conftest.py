"""Shared set-up of the benchmark's CPU tests: the checkout's root and
``src`` on the path, a small eta model in a directory of the tests' own so
a cell's search loads it instead of training the full one, and the
``cpu_devices(n)`` mark.

A test marked ``cpu_devices(n)`` (a cell on four chips, a reference split
over four devices) needs ``n`` devices. The test process keeps the one CPU
device that the rest of the suite expects, so such tests run in a child
pytest whose CPU backend has ``n`` virtual devices
(``--xla_force_host_platform_device_count``): the first of them in a module
runs all of that module's, and each asserts its own outcome there."""
import os
import subprocess
import sys
import tempfile
import xml.etree.ElementTree as ET

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

_CHILD_OUTCOMES: dict = {}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "cpu_devices(n): needs n CPU devices; run in a child pytest that has them")


def _devices_needed(item):
    mark = item.get_closest_marker("cpu_devices")
    if mark is None:
        return None
    import jax

    n = mark.args[0]
    return n if len(jax.devices()) < n else None


def _outcomes(item, n: int) -> dict:
    """Outcome of every test of ``item``'s module that needs ``n`` devices,
    from one child pytest: {nodeid: (passed, report text)}."""
    key = (str(item.path), n)
    if key not in _CHILD_OUTCOMES:
        ids = [it.nodeid for it in item.session.items
               if it.path == item.path and _devices_needed(it) == n]
        env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
        env.update(JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
        found, cases = {}, []
        with tempfile.TemporaryDirectory() as tmp:
            xml = os.path.join(tmp, "child.xml")
            p = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
                                "-p", "no:randomly", f"--junitxml={xml}", *ids],
                               cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
            if os.path.exists(xml):
                cases = list(ET.parse(xml).getroot().iter("testcase"))
        for case in cases:
            path = case.get("classname").replace(".", "/") + ".py"
            bad = [c for c in case if c.tag in ("failure", "error", "skipped")]
            found[f"{path}::{case.get('name')}"] = (
                not bad, "\n".join((c.get("message") or "") + (c.text or "") for c in bad))
        _CHILD_OUTCOMES[key] = {i: found.get(i, (False, p.stdout[-4000:] + p.stderr[-4000:]))
                                for i in ids}
    return _CHILD_OUTCOMES[key]


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    n = _devices_needed(pyfuncitem)
    if n is None:
        return None
    passed, text = _outcomes(pyfuncitem, n)[pyfuncitem.nodeid]
    assert passed, f"in a child pytest with {n} CPU devices:\n{text[-6000:]}"
    return True


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
