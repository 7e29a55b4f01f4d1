"""Scheduler: 90th percentile of the wait from a request's scheduled
arrival to the start of its insertion, over the window's requests (one
not started by the close counts at the close), in ms."""
from lib.readers import p90


def read(ctx):
    sv = ctx.serve
    if sv is None or ctx.mix["kind"] != "open_loop":
        return None
    xs = [min(r.insert_start if r.insert_start is not None else sv["t_end"],
              sv["t_end"]) - r.due for r in sv["recs"].values()]
    return 1e3 * p90(xs) if xs else None
