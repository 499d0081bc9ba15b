"""Share of the serving window spent in the engine's prefill phase (the
admitted requests' prefills, cache inserts and first tokens), in %: the
window's part of the ``lm_serve.prefill_seconds`` counter."""


def read(ctx):
    secs = ctx.counts.get("prefill_seconds")
    if not secs or ctx.window_s <= 0:
        return None
    return 100.0 * secs / ctx.window_s
