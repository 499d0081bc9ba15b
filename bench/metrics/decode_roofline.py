"""Decode's share of its roofline in the fused step.

The least time the decode could take is its bytes over the chip's HBM
bandwidth: the batch's logical compressed bytes (counted from the stored
plane counts) plus its float32 output.  The time it took is the device time
of the ops under the ``gather_decode`` scope inside the traced window.
"""
from bench.metrics_common import bandwidth_share


def read(ctx):
    t = ctx.trace_summary
    if t is None:
        return None
    nbytes = ctx.counts["decode_bytes_per_sample"] * ctx.counts["samples"]
    return bandwidth_share(ctx, nbytes, t.scope_s("gather_decode"))
