"""Arithmetic the per-layer metric readers share."""
from __future__ import annotations


def idle_share(ctx):
    """Percent of the traced window with no device operation running."""
    t = ctx.trace_summary
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def bandwidth_share(ctx, nbytes: float, seconds: float):
    """Percent of the chip's HBM bandwidth that ``nbytes`` in ``seconds``
    would take; None when nothing was timed."""
    if seconds <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / seconds / ctx.peaks["hbm_bytes_per_s"]
