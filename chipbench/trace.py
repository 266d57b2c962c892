"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is read into flat events (plane, line, name, start, end; times in
ns on the profiler's one clock). Device planes are ``/device:...``; their
``XLA Ops`` line holds the operations that ran, their ``XLA Modules`` line
the compiled programs. The host plane holds the benchmark's spans (see
``spans.py``), the traced window among them.

    busy        union of a device's op intervals inside the window
    idle share  1 - busy / window
    per module  busy time inside each run of a program, and the run count
    exposed     time in collectives with no other op running on that device
    gaps        idle intervals, each named by the host span that covers most
                of it
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from typing import NamedTuple

import numpy as np

WINDOW = "chipbench.window"
# each traced run writes its profile to a directory of its own under here,
# read back and removed in the same run
ARTIFACTS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "artifacts")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float
    end: float


def short(name: str) -> str:
    """An op's name without the HLO text the TPU trace appends to some
    (``%while.170 = (s32[] ...`` -> ``while.170``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def from_profile(pd) -> list[Event]:
    """Events of a ``jax.profiler.ProfileData``."""
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                s = float(ev.start_ns)
                out.append(Event(plane.name, line.name, short(ev.name), s,
                                 s + float(ev.duration_ns)))
    return out


def load(log_dir: str) -> list[Event]:
    """Events of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(max(files, key=os.path.getmtime)))


def capture(fn, span_names) -> dict:
    """Run ``fn`` under the profiler and return the reduction of its trace;
    the trace's directory is made for this run alone and removed after."""
    import jax

    os.makedirs(ARTIFACTS, exist_ok=True)
    log_dir = tempfile.mkdtemp(prefix="chipbench_trace.", dir=ARTIFACTS)
    try:
        jax.profiler.start_trace(log_dir)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        return reduce(load(log_dir), span_names)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


# --- interval arithmetic ----------------------------------------------------

def _merge_np(starts, ends):
    """Merged union of intervals given as arrays: (starts, ends)."""
    s, e = np.asarray(starts, float), np.asarray(ends, float)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    first = np.ones(len(s), bool)
    first[1:] = s[1:] > reach[:-1]
    heads = np.flatnonzero(first)
    tails = np.r_[heads[1:] - 1, len(s) - 1]
    return s[heads], reach[tails]


def merge(intervals) -> list[tuple[float, float]]:
    """Union of intervals as sorted, disjoint (start, end) pairs."""
    arr = np.asarray(list(intervals), float).reshape(-1, 2)
    s, e = _merge_np(arr[:, 0], arr[:, 1])
    return list(zip(s.tolist(), e.tolist()))


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of merged intervals ``a`` not covered by merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    return subtract([(lo, hi)], merge(busy))


# --- the reduction ------------------------------------------------------------

def window(events) -> tuple[float, float]:
    for ev in events:
        if ev.name == WINDOW and not ev.plane.startswith("/device:"):
            return ev.start, ev.end
    raise ValueError(f"no {WINDOW!r} span in the trace")


CPU_OPS_LINE = "tf_XLAPjRtCpuClient"


def _stream(ev: Event):
    """The device an op ran on: a ``/device:`` plane's ops line, or, for the
    CPU backend (tests), the host thread that runs its programs."""
    if ev.plane.startswith("/device:"):
        return ev.plane if ev.line == OPS_LINE else None
    return "cpu" if ev.line.startswith(CPU_OPS_LINE) else None


def _covered(ms, me, lo, hi) -> float:
    """Length of [lo, hi] covered by merged intervals (ms, me)."""
    a = max(np.searchsorted(me, lo, side="right"), 0)
    b = np.searchsorted(ms, hi, side="left")
    if b <= a:
        return 0.0
    return float(np.sum(np.minimum(me[a:b], hi) - np.maximum(ms[a:b], lo)))


def reduce(events, span_names=()) -> dict:
    """Everything the metric readers take from one traced window. Times in
    seconds; per-device numbers averaged over the devices that ran ops."""
    lo, hi = window(events)
    names = set(span_names)
    ops: dict = {}
    modules: dict = {}
    host = []
    for ev in events:
        if ev.end <= lo or ev.start >= hi:
            continue
        d = _stream(ev)
        if d is not None:
            ops.setdefault(d, []).append(ev)
        elif ev.plane.startswith("/device:"):
            if ev.line == MODULES_LINE and ev.start >= lo and ev.end <= hi:
                modules.setdefault(ev.plane, []).append(ev)
        elif ev.name in names:
            host.append(ev)
    if not ops:
        raise ValueError("no device ran an operation in the trace")
    devs = sorted(ops)
    n = len(devs)
    is_coll: dict = {}
    busy_total = coll_total = exposed = 0.0
    per_op: dict = {}
    per_module: dict = {}
    first_busy = None
    runs: dict = {}
    for d in devs:
        evs = ops[d]
        st = np.clip([e.start for e in evs], lo, hi)
        en = np.clip([e.end for e in evs], lo, hi)
        coll = np.array([is_coll.setdefault(e.name, bool(COLLECTIVE.search(e.name)))
                         for e in evs], bool)
        ms, me = _merge_np(st, en)
        busy_total += float(np.sum(me - ms))
        if first_busy is None:
            first_busy = list(zip(ms, me))
        cs, ce = _merge_np(st[coll], en[coll])
        ks, ke = _merge_np(st[~coll], en[~coll])
        coll_total += float(np.sum(ce - cs))
        exposed += total(subtract(list(zip(cs, ce)), list(zip(ks, ke))))
        for e, a, b in zip(evs, st, en):
            per_op[e.name] = per_op.get(e.name, 0.0) + (b - a)
        for ev in sorted(modules.get(d, []), key=lambda e: e.start):
            name = _module_name(ev.name)
            m = per_module.setdefault(name, {"runs": 0, "busy": 0.0, "wall": 0.0})
            m["runs"] += 1
            m["busy"] += _covered(ms, me, ev.start, ev.end)
            m["wall"] += ev.end - ev.start
            if d == devs[0]:
                runs.setdefault(name, []).append(
                    (_covered(ms, me, ev.start, ev.end) * 1e-9, (ev.end - ev.start) * 1e-9))

    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:10]
    named = [[_cover(g, host), (g[1] - g[0]) * 1e-9] for g in idle]
    ns = 1e-9
    return {
        "devices": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy_total / n * ns,
        "modules": {k: {"runs": v["runs"] / n, "busy_s": v["busy"] / n * ns,
                        "wall_s": v["wall"] / n * ns} for k, v in per_module.items()},
        "collective_s": coll_total / n * ns,
        "collective_exposed_s": exposed / n * ns,
        "runs": runs,
        "dispatches": [ev.name for ev in sorted(host, key=lambda e: e.start)],
        "top_ops": [[k, v / n * ns] for k, v in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": named,
    }


def _module_name(name: str) -> str:
    """``jit_train_step(123)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def _cover(gap, host) -> str:
    best, name = 0.0, "none"
    for ev in host:
        ov = min(gap[1], ev.end) - max(gap[0], ev.start)
        if ov > best:
            best, name = ov, ev.name
    return name


def runs_by_dispatch(red: dict, label: str, labels: tuple) -> list:
    """(busy_s, wall_s) of each run, on the first device, of the program
    that ``label``'s host spans dispatched, where the programs of ``labels``
    carry no telling name (a jitted ``functools.partial`` is ``jit__unknown``):
    the device runs programs in the order the host dispatched them, so the
    program with one run per span of ``labels`` is paired with them in
    order. Empty where no program matches."""
    disp = [n for n in red["dispatches"] if n in labels]
    match = [runs for runs in red["runs"].values() if len(runs) == len(disp) and disp]
    if not match:
        return []
    # a small program can run as often (a slice of each step's logits):
    # the one that does the work is the busiest
    runs = max(match, key=lambda rs: sum(b for b, _ in rs))
    return [r for r, n in zip(runs, disp) if n == label]


def module_stats(red: dict, pattern: str) -> tuple[float, float, float]:
    """(runs, busy_s, wall_s) summed over programs whose name matches."""
    runs = busy = wall = 0.0
    for k, v in red["modules"].items():
        if re.search(pattern, k):
            runs += v["runs"]
            busy += v["busy_s"]
            wall += v["wall_s"]
    return runs, busy, wall
