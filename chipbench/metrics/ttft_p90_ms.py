"""90th percentile over every request that arrived in the window of the
time from its scheduled arrival to its first token on the host, in ms; a
request with no first token when the window closes counts at the close."""
from lib.readers import p90


def read(ctx):
    sv = ctx.serve
    if sv is None or ctx.mix["kind"] != "open_loop":
        return None
    xs = [min(r.first if r.first is not None else sv["t_end"], sv["t_end"])
          - r.due for r in sv["recs"].values()]
    return 1e3 * p90(xs) if xs else None
