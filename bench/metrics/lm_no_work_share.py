"""Share of the LM serving window in which no slot was active and the engine
slept until the next arrival, in %: the window's part of the
``lm_serve.no_work_seconds`` counter."""


def read(ctx):
    secs = ctx.counts.get("no_work_seconds")
    if not secs or ctx.window_s <= 0:
        return None
    return 100.0 * secs / ctx.window_s
