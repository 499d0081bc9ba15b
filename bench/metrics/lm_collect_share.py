"""Share of the LM serving window spent in the engine's bookkeeping between
steps (admission, appends, finished requests, refill), in %: the window's
part of the ``lm_serve.collect_seconds`` counter."""


def read(ctx):
    secs = ctx.counts.get("collect_seconds")
    if not secs or ctx.window_s <= 0:
        return None
    return 100.0 * secs / ctx.window_s
