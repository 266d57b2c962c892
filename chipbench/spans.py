"""The benchmark's own host spans: each ``span(name)`` records its start and
end on the host clock and, while the profiler runs, appears in the trace
under the same name, so idle gaps of the device can be laid against what
the host was doing."""
from __future__ import annotations

import contextlib
import time

import jax


class Spans:
    def __init__(self):
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str, since: float = float("-inf"),
                  until: float = float("inf")) -> list[float]:
        return [b - a for n, a, b in self.records
                if n == name and a >= since and b <= until]
