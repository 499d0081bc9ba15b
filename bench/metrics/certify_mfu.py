"""The whole certification stage's share of the chip's peak.

The stage is integer and bit work with no FLOPs to speak of, so its bound
is HBM bytes: each member's float32 input read once plus its logical
compressed output written once, at the rate the traced window certified
members, over the chip's HBM bandwidth.
"""
from bench.metrics_common import bandwidth_share


def read(ctx):
    c = ctx.counts
    if not c.get("members"):
        return None
    return bandwidth_share(ctx, c["encode_bytes_per_member"] * c["members"],
                           ctx.window_s)
