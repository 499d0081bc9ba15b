"""Phase clock shared by the serving engines' loops.

A loop pass is split into named phases that partition its wall time.  Each
phase is an ``obs.trace`` span (so it lands on the profiler's timeline as
``<prefix>.<phase>``) and a cumulative ``<prefix>.<phase>_seconds`` counter
in the global registry, always on.
"""
from __future__ import annotations

import contextlib
import time
from typing import Sequence

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


class PhaseClock:
    """Cumulative seconds per phase of a loop on one chained clock: a phase
    is charged from the end of the phase before it to its own end, so the
    phases partition the loop's wall time from ``t0``."""

    def __init__(self, prefix: str, phases: Sequence[str], t0: float):
        reg = obs_metrics.get_registry()
        self._prefix = prefix
        self._counters = {p: reg.counter(f"{prefix}.{p}_seconds")
                          for p in phases}
        self._mark = t0

    @contextlib.contextmanager
    def __call__(self, phase: str):
        with obs_trace.span(f"{self._prefix}.{phase}", cat="serve"):
            yield
        now = time.perf_counter()
        self._counters[phase].add(now - self._mark)
        self._mark = now
