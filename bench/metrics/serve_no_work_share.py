"""Share of the serving window in which no query was in flight and the
engine slept until the next arrival, in %: the
``surrogate_serve.no_work_seconds`` counter over the window.  At a fixed
offered rate, an engine that drains queries faster leaves more of it.

Set-up's single warm query arrives at once, so it adds none."""
from repro.obs.metrics import get_registry


def read(ctx):
    secs = get_registry().snapshot().get("surrogate_serve.no_work_seconds")
    if not secs or ctx.window_s <= 0:
        return None
    return 100.0 * secs / ctx.window_s
