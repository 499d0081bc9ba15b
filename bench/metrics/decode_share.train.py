"""Share of the fused step's device time spent under ``gather_decode``."""


def read(ctx):
    t = ctx.trace_summary
    if t is None:
        return None
    _, step_s = t.module_s("_fused_step")
    if step_s <= 0:
        return None
    return 100.0 * t.scope_s("gather_decode") / step_s
