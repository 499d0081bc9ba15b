"""JAX-side observability: scopes, profiler capture, recompile detection.

Two tools, both safe to leave wired in production code (host-side region
markers are ``obs.trace.span``, which is also a profiler annotation):

  * :func:`profiler_trace` -- the opt-in ``jax.profiler.trace`` capture
    (TensorBoard/XProf protos next to our own Chrome trace); a trace that
    was asked for and cannot start or stop raises, so a run never reports
    a capture it does not have;
  * :class:`RecompileWatcher` -- tracks the ``jit`` cache size of registered
    functions and flags *unexpected* growth.  Silent retracing is the real
    footgun this repo has already been bitten by (the serving engines once
    recompiled per engine instance until their jits moved to module level):
    a weak-shaped operand or an unhashable static arg quietly multiplies
    compile time.  ``watch()`` registers a function, ``rebase()`` accepts
    the current cache as expected (call it after warmup), ``check()``
    returns every function whose cache grew since -- and mirrors each event
    into the metrics registry (``jax.recompiles`` counter) and the tracer
    (``recompile`` instant) so traces carry the flag too.

``named_scope`` is re-exported so modules below ``models`` in the layer
ladder can name HLO regions without importing jax utilities ad hoc.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional

import jax

from repro.obs import trace as _trace
from repro.obs.metrics import MetricsRegistry, get_registry

named_scope = jax.named_scope


@contextlib.contextmanager
def profiler_trace(log_dir: Optional[str]):
    """Opt-in native JAX profiler capture (no-op when ``log_dir`` is None).

    Yields whether a trace is being captured.  Failing to start or stop a
    requested trace raises.
    """
    if log_dir is None:
        yield False
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()


def jit_cache_size(fn) -> Optional[int]:
    """Compile-cache entry count of a ``jax.jit``-wrapped function (None when
    the wrapper doesn't expose one, e.g. a plain Python callable)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


@dataclasses.dataclass
class RecompileEvent:
    name: str
    before: int
    after: int

    @property
    def growth(self) -> int:
        return self.after - self.before


class RecompileWatcher:
    """Flags jit cache growth on registered functions.

    Typical wiring (the train loop and serving engines do exactly this):

        watcher.watch("train.fused_step", _fused_step)
        ... first step (expected compile) ...
        watcher.rebase()
        ... steady state ...
        events = watcher.check()     # non-empty => unexpected recompiles
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._fns: Dict[str, object] = {}
        self._baseline: Dict[str, int] = {}
        self._registry = registry

    def _reg(self) -> MetricsRegistry:
        return self._registry if self._registry is not None else get_registry()

    def watch(self, name: str, fn) -> None:
        """Register ``fn`` under ``name``; current cache size is the baseline."""
        if jit_cache_size(fn) is None:
            raise TypeError(f"{name}: not a jitted function "
                            "(no _cache_size); wrap with jax.jit first")
        self._fns[name] = fn
        self._baseline[name] = jit_cache_size(fn)

    def sizes(self) -> Dict[str, int]:
        return {name: jit_cache_size(fn) for name, fn in self._fns.items()}

    def rebase(self) -> None:
        """Accept the current cache sizes as expected (post-warmup)."""
        self._baseline = self.sizes()

    def check(self) -> List[RecompileEvent]:
        """Every watched function whose cache grew since the last baseline.

        Each event increments the ``jax.recompiles`` counter and emits a
        ``recompile`` tracer instant, then the baseline absorbs the growth
        (one flag per recompile, not one per check).
        """
        events = []
        for name, after in self.sizes().items():
            before = self._baseline.get(name, 0)
            if after > before:
                events.append(RecompileEvent(name, before, after))
                self._reg().counter("jax.recompiles").add(after - before)
                _trace.instant("recompile", cat="jax", fn=name,
                               before=before, after=after)
                self._baseline[name] = after
        return events


# Shared process-wide watcher: layers register their module-level jitted
# steps here so one ``check()`` (end of a train run / serve loop / benchmark
# module) covers every hot function without plumbing a watcher through.
_WATCHER = RecompileWatcher()


def get_watcher() -> RecompileWatcher:
    return _WATCHER
