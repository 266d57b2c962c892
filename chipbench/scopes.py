"""Reduction of a profiler trace to the names the program gives its work.

The program runs each layer kind's ops under a ``jax.named_scope`` (``SCOPES``),
which the compiled program keeps in every op's metadata as an op-name path,
e.g. ``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/attn/dot_general``;
its host spans are named ``serve.*``, ``data.*`` and ``search*``. The trace's
device ops carry only the op's name (``fusion.220``), so the paths come from
the compiled programs' HLO text (``op_paths``).

    scopes             device self time in the window by scope, averaged over
                       devices: where ops nest (a ``while`` holds its body's
                       ops) only the innermost counts; an op counts to the
                       innermost scope of its path, time under none to
                       ``other``
    program_spans      durations of the program's host spans, by name
    idle_gaps_program  the ten longest idle gaps of the first device, each
                       named by the innermost program span covering most of it
    idle_under         idle time of the first device under the program's host
                       spans of each name
    run_gaps           idle time between consecutive runs of one program on
                       the first device

Times in the events are ns (``trace.Event``); results are in seconds.
"""
from __future__ import annotations

import re

import numpy as np

from chipbench import trace as tr

SCOPES = ("embed", "attn", "ffn", "moe", "ssd", "head", "optimizer")
OTHER = "other"
PROGRAM_SPAN = re.compile(r"serve\.|data\.|search")
# the differentiation wrappers of a path's part: ``transpose(jvp(head))``
_WRAPPERS = re.compile(r"^(?:[\w.]+\()+|\)+$")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"', re.M)


def op_paths(hlo_text: str) -> dict:
    """Op name -> op-name path, from a compiled program's HLO text."""
    return dict(_INSTRUCTION.findall(hlo_text))


def scope_of(path: str) -> str:
    """The innermost layer scope of an op-name path, or ``other``."""
    for part in reversed(path.split("/")):
        name = _WRAPPERS.sub("", part)
        if name in SCOPES:
            return name
    return OTHER


def _self_times(starts, ends) -> np.ndarray:
    """Each interval's length less the parts its nested intervals cover
    (an interval nests in the latest-started one still open)."""
    order = np.lexsort((-ends, starts))
    own = ends - starts
    stack: list[int] = []
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(ends[i], ends[stack[-1]]) - starts[i]
        stack.append(i)
    return own


def _device_ops(events, lo, hi) -> dict:
    ops: dict = {}
    for ev in events:
        if ev.end > lo and ev.start < hi:
            d = tr._stream(ev)
            if d is not None:
                ops.setdefault(d, []).append(ev)
    return ops


def scopes(events, paths: dict, lo: float, hi: float) -> dict:
    """Device self time (s) by scope in [lo, hi], averaged over devices;
    ``paths`` maps op names to their op-name paths."""
    ops = _device_ops(events, lo, hi)
    out = dict.fromkeys(SCOPES + (OTHER,), 0.0)
    for evs in ops.values():
        st = np.clip([e.start for e in evs], lo, hi)
        en = np.clip([e.end for e in evs], lo, hi)
        for ev, own in zip(evs, _self_times(st, en)):
            out[scope_of(paths.get(ev.name, ""))] += own
    return {k: v / max(len(ops), 1) * 1e-9 for k, v in out.items()}


def _program_spans(events, lo, hi) -> list:
    return [ev for ev in events if not ev.plane.startswith("/device:")
            and PROGRAM_SPAN.match(ev.name) and ev.end > lo and ev.start < hi]


def _idle(events, lo, hi) -> list:
    """Idle intervals of the first device in [lo, hi]."""
    ops = _device_ops(events, lo, hi)
    return tr.gaps([(e.start, e.end) for e in ops[min(ops)]], lo, hi)


def program_spans(events, lo: float, hi: float) -> dict:
    """Durations (s) of the program's host spans in [lo, hi], by name."""
    out: dict = {}
    for ev in sorted(_program_spans(events, lo, hi), key=lambda e: e.start):
        out.setdefault(ev.name, []).append((ev.end - ev.start) * 1e-9)
    return out


def _name(gap, spans) -> str:
    """The span covering most of the gap; of spans covering it alike, the
    innermost (shortest)."""
    best, name = (0.0, 0.0), "none"
    for ev in spans:
        key = (min(gap[1], ev.end) - max(gap[0], ev.start), ev.start - ev.end)
        if key[0] > 0 and key > best:
            best, name = key, ev.name
    return name


def idle_gaps_program(events, lo: float, hi: float, n: int = 10) -> list:
    """[span name, seconds] of the ``n`` longest idle gaps of the first device."""
    spans = _program_spans(events, lo, hi)
    return [[_name(g, spans), (g[1] - g[0]) * 1e-9]
            for g in sorted(_idle(events, lo, hi), key=lambda g: g[0] - g[1])[:n]]


def idle_under(events, lo: float, hi: float) -> dict:
    """Idle time (s) of the first device in [lo, hi] under the program's
    host spans of each name (a nested span's time counts to it and to the
    span around it)."""
    idle = _idle(events, lo, hi)
    spans: dict = {}
    for ev in _program_spans(events, lo, hi):
        spans.setdefault(ev.name, []).append((ev.start, ev.end))
    return {k: (tr.total(idle) - tr.total(tr.subtract(idle, tr.merge(v)))) * 1e-9
            for k, v in spans.items()}


def run_gaps(events, pattern: str, lo: float, hi: float) -> list:
    """Device-idle time (s) between each run of a program whose name matches
    ``pattern`` and its next run, on the first device that ran it."""
    runs: dict = {}
    for ev in events:
        if ev.plane.startswith("/device:") and ev.line == tr.MODULES_LINE \
                and lo <= ev.start and ev.end <= hi and re.search(pattern, ev.name):
            runs.setdefault(ev.plane, []).append((ev.start, ev.end))
    if not runs:
        return []
    plane = min(runs)
    busy = tr.merge((e.start, e.end) for e in _device_ops(events, lo, hi).get(plane, []))
    runs = sorted(runs[plane])
    return [tr.total(tr.gaps(busy, a[1], b[0])) * 1e-9 for a, b in zip(runs, runs[1:])]
