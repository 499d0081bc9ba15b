"""Share of the LM serving window spent reading each decode step's tokens
(and the kept logits rows) back to the host, in %: the window's part of the
engine's ``lm_serve.fetch_seconds`` counter."""


def read(ctx):
    secs = ctx.counts.get("fetch_seconds")
    if not secs or ctx.window_s <= 0:
        return None
    return 100.0 * secs / ctx.window_s
