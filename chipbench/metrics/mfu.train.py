"""Whole step: forward and backward model FLOPs of the window's tokens
over the window times the chips times the chip's peak, in %."""
from lib import flops
from lib.readers import pct


def read(ctx):
    if ctx.peaks is None:
        return None
    if ctx.train is None or not ctx.train["steps"]:
        return None
    f = flops.train_flops_per_token(ctx.m, ctx.mix["seq"]) * \
        ctx.train["tokens"]
    return pct(f / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops"]))
