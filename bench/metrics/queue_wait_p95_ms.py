"""95th percentile of the time queries waited for a slot: the engine's seat
stamp less the scheduled arrival."""


def read(ctx):
    return ctx.counts.get("queue_wait_p95_ms")
