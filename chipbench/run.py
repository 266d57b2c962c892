"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload yi-6b.train.s4k --seed 7 --seconds 30 --trace 0

Everything about the cell is data: ``BENCHMARK.json`` names its
configuration (``chipbench/configs/<config>.json``) and traffic mix
(``chipbench/traffic/<traffic>.json``); the mix names its mode, whose driver
is ``chipbench/modes/<mode>.py``; its limits are in
``chipbench/limits/<workload>.json``; each per-layer metric is read by
``chipbench/metrics/<metric>.py``. With ``--trace 0`` the line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of a further short window.

There is no CPU fallback: without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result. The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` or ``artifacts/jax_cache`` in the checkout, and
the search's eta model is cached in ``artifacts/``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# libtpu would otherwise write its logs to a fixed /tmp/tpu_logs
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class CellError(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    """One cell as its driver sees it."""

    workload: str
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    arch: object
    spans: object
    t0: float


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(workload: str, bench_path: str = os.path.join(ROOT, "BENCHMARK.json")) -> dict:
    bench = _load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in {bench_path}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return {
        "workload": w,
        "config": _load_json(os.path.join(ROOT, conf["file"])),
        "traffic": _load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
        "limits": _load_json(os.path.join(HERE, "limits", workload + ".json")),
        "per_layer": per_layer,
        "end_to_end": end_to_end,
    }


def _read_metric(name: str, ctx: dict):
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"),
        os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _rehearsal(files: dict) -> None:
    """Shrink a cell to the program's reduced preset of its family and the
    mix's ``rehearsal`` sizes (CPU tests only)."""
    from repro.configs import get_reduced

    a = files["config"]["arch"]
    small = get_reduced(files["config"]["program_preset"])
    files["arch"] = dataclasses.replace(small, tie_embeddings=a.get("tie_embeddings", False))
    files["traffic"] = {**files["traffic"], **files["traffic"].get("rehearsal", {})}


def make_cell(workload: str, seed: int, seconds: float, trace: bool, *,
              rehearse: bool = False, t0: float = T0):
    """The cell and its files; refuses a cell whose chips JAX cannot find.
    ``rehearse`` (CPU tests only) skips that look and shrinks the cell."""
    files = cell_files(workload)
    chips = files["workload"]["chips"]
    import jax

    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu" or len(devices) < chips):
        raise CellError(f"cell {workload} needs {chips} TPU chip(s); JAX found "
                        f"{len(devices)} {devices[0].platform} device(s)")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.core.arch import ModelArch
    from repro.launch.compile_cache import enable_compile_cache

    from chipbench.spans import Spans

    enable_compile_cache()
    if rehearse:
        _rehearsal(files)
    else:
        files["arch"] = ModelArch(**files["config"]["arch"])
    cell = Cell(workload=workload, config=files["config"], traffic=files["traffic"],
                limits=files["limits"], seed=seed, seconds=seconds, trace=trace,
                devices=devices[:chips], arch=files["arch"], spans=Spans(), t0=t0)
    return cell, files


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            rehearse: bool = False, t0: float = T0) -> dict:
    """Run the cell; return the result line as a dict (``check`` last)."""
    cell, files = make_cell(workload, seed, seconds, trace, rehearse=rehearse, t0=t0)
    from chipbench import count

    devices = cell.devices
    mode = importlib.import_module("chipbench.modes." + files["traffic"]["mode"])
    out = mode.run(cell)
    gc.collect()

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        ctx = dict(out["ctx"], peaks=None if rehearse else count.peaks(kind),
                   chips=len(cell.devices), spans=cell.spans)
        metrics = {}
        for m in files["per_layer"]:
            v = _read_metric(m["name"], ctx)
            if v is None:
                # left out of the line, as the contract asks, but said aloud
                print(f"chipbench: per-layer metric {m['name']} found nothing to read",
                      file=sys.stderr)
            else:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        red = out["ctx"]["trace"]
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": red["top_ops"], "idle_gaps": red["idle_gaps"]}
    else:
        result["metrics"] = {m["name"]: {"value": out["e2e"][m["name"]], "unit": m["unit"]}
                             for m in files["end_to_end"]}
        result["device"] = device
    result["check"] = out["check"]
    print("timing " + json.dumps(dict(out["timing"], total_s=time.perf_counter() - t0)),
          file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds, bool(args.trace))
    except CellError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from chipbench.check import print_numbers

    print_numbers(result["check"])
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
