"""Programs: the decode burst's share of its roofline: the least time the
chip could take for the window's decode steps (the larger of their model
FLOPs over peak FLOP/s and the weights plus the live positions' K/V over
peak HBM bandwidth) over the burst program's device time in the trace,
in %."""
from lib.readers import decode_work, pct, window_bursts


def read(ctx):
    if ctx.peaks is None:
        return None
    if ctx.trace is None or ctx.serve is None:
        return None
    t = ctx.trace["programs"].get("burst")
    bs = window_bursts(ctx)
    if not t or not bs:
        return None
    f, b = decode_work(ctx, bs)
    pk = ctx.peaks
    return pct(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"]) / t)
