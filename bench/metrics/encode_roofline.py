"""The fixed-accuracy encode kernel's share of its roofline.

The least time is the float32 input plus the logical compressed output
(counted from plane counts) over the chip's HBM bandwidth; the time taken is
the device time of the kernel's custom call (``kernels/ops.py`` names it
``zfp_encode_blocks_fa``) in the traced window.
"""
from bench.metrics_common import bandwidth_share

KERNEL = "zfp_encode_blocks_fa"


def read(ctx):
    t = ctx.trace_summary
    if t is None:
        return None
    c = ctx.counts
    nbytes = c["encode_bytes_per_member"] * c["members"]
    return bandwidth_share(ctx, nbytes, t.kernel_s(KERNEL))
