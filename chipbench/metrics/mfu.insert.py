"""Whole step: model FLOPs of the window's insertions over their host
time (the program's serve.prefill_seconds) times the chip's peak, in %."""
from lib.readers import hist_delta, insert_work, pct


def read(ctx):
    if ctx.peaks is None:
        return None
    if ctx.serve is None:
        return None
    work, d = insert_work(ctx), hist_delta(ctx, "serve.prefill_seconds")
    if work is None or d is None:
        return None
    return pct(work[0] / (d[1] * ctx.peaks["bf16_flops"]))
