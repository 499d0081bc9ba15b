"""The LM server's share of the chip's peak FLOP rate: the prefill and
decode FLOPs of the window's requests (``bench/lm_work.py``) over the
window's time and the bf16 peak."""


def read(ctx):
    flops = ctx.counts.get("flops")
    if not flops or ctx.window_s <= 0:
        return None
    return 100.0 * flops / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
