"""Programs: the insertion program's share of its roofline: the least
time the chip could take for the window's insertions (the larger of their
model FLOPs over peak FLOP/s and their bytes over peak HBM bandwidth)
over the insertion program's device time in the trace, in %."""
from lib.readers import insert_work, pct


def read(ctx):
    if ctx.peaks is None:
        return None
    if ctx.trace is None or ctx.serve is None:
        return None
    t = ctx.trace["programs"].get("prefill_into")
    work = insert_work(ctx)
    if not t or work is None:
        return None
    f, b = work
    pk = ctx.peaks
    return pct(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"]) / t)
