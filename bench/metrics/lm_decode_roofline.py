"""The LM decode step's share of its roofline.

The least time the window's decode steps could take is their logical bytes
(``bench/lm_work.py``: weights once a step, each active slot's keys and
values up to its depth, every slot's SSM and conv state read and written)
over the chip's HBM bandwidth.  The time they took is the device time of
the ``_decode_step`` programs inside the traced window."""
from bench.metrics_common import bandwidth_share


def read(ctx):
    t = ctx.trace_summary
    if t is None or not ctx.counts.get("decode_bytes"):
        return None
    return bandwidth_share(ctx, ctx.counts["decode_bytes"],
                           t.module_s("_decode_step")[1])
