"""Scheduler: share of the window's slot-steps that decoded a live
request (the decode bursts' own live counts, as serve.slot_idle_steps
counts their complement), in %."""
from lib.readers import pct, window_bursts


def read(ctx):
    if ctx.serve is None:
        return None
    bs = window_bursts(ctx)
    slots = ctx.mix["serve"]["slots"]
    total = sum(b.k * slots for b in bs)
    return pct(sum(b.live_steps for b in bs) / total) if total else None
