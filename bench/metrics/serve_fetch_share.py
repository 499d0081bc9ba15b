"""Share of the serving window spent copying each fleet step's mean and band
from the device to the host, in %: the engine's
``surrogate_serve.fetch_seconds`` counter over the window.

The counter is the process's, so set-up's single warm query adds one fleet
step's fetch to the window's."""
from repro.obs.metrics import get_registry


def read(ctx):
    secs = get_registry().snapshot().get("surrogate_serve.fetch_seconds")
    if not secs or ctx.window_s <= 0:
        return None
    return 100.0 * secs / ctx.window_s
