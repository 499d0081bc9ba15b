"""The whole training step's share of the chip's peak FLOP rate.

Three forward passes' FLOPs per sample (``bench/work.py``) times the samples
the traced window trained, over the window's time and the bf16 peak.
"""


def read(ctx):
    c = ctx.counts
    if not c.get("samples") or ctx.window_s <= 0:
        return None
    rate = c["train_flops_per_sample"] * c["samples"] / ctx.window_s
    return 100.0 * rate / ctx.peaks["bf16_flops_per_s"]
