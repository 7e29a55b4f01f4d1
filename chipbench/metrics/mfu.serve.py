"""Whole step: model FLOPs of every token prefilled and decoded in the
window over the window times the chip's peak, in %."""
from lib.readers import decode_work, insert_work, pct, window_bursts


def read(ctx):
    if ctx.peaks is None:
        return None
    if ctx.serve is None:
        return None
    bs = window_bursts(ctx)
    if not bs:
        return None
    f = decode_work(ctx, bs)[0] + (insert_work(ctx) or (0.0, 0))[0]
    return pct(f / (ctx.window_s * ctx.peaks["bf16_flops"]))
