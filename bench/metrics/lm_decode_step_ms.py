"""Device time per LM decode step (the ``_decode_step`` program: every
layer over every slot, one token each), in ms."""


def read(ctx):
    t = ctx.trace_summary
    if t is None:
        return None
    calls, secs = t.module_s("_decode_step")
    if calls == 0:
        return None
    return 1000.0 * secs / calls
