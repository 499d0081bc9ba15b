"""Share of the serving window spent on the engine's host bookkeeping, in %:
admission, cond rows, per-slot appends, stacking finished rollouts and slot
refill (the ``surrogate_serve.collect_seconds`` counter over the window).

The counter is the process's, so set-up's single warm query adds one fleet
step's collect to the window's."""
from repro.obs.metrics import get_registry


def read(ctx):
    secs = get_registry().snapshot().get("surrogate_serve.collect_seconds")
    if not secs or ctx.window_s <= 0:
        return None
    return 100.0 * secs / ctx.window_s
