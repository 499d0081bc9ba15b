"""Device time of Algorithm 1's search program per member, in ms."""


def read(ctx):
    t = ctx.trace_summary
    members = ctx.counts.get("members")
    if t is None or not members:
        return None
    calls, secs = t.module_s("_search_batch")
    if calls == 0:
        return None
    return 1000.0 * secs / members
