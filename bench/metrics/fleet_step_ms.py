"""Device time per fleet step (one vmapped forward of every member over the
slot batch), in ms."""


def read(ctx):
    t = ctx.trace_summary
    if t is None:
        return None
    calls, secs = t.module_s("_fleet_step")
    if calls == 0:
        return None
    return 1000.0 * secs / calls
