"""The served fleet's share of the chip's peak FLOP rate: forward FLOPs per
member evaluation (``bench/work.py``) times the evaluations the traced
window answered, over the window's time and the bf16 peak."""


def read(ctx):
    c = ctx.counts
    if not c.get("member_evals") or ctx.window_s <= 0:
        return None
    rate = c["forward_flops"] * c["member_evals"] / ctx.window_s
    return 100.0 * rate / ctx.peaks["bf16_flops_per_s"]
