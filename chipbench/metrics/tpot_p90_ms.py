"""90th percentile over the requests finished in the window of (finish -
first token) / (tokens - 1), in ms."""
from lib.readers import p90


def read(ctx):
    sv = ctx.serve
    if sv is None or ctx.mix["kind"] != "open_loop":
        return None
    xs = [(r.finish - r.first) / (r.max_new - 1)
          for r in sv["recs"].values()
          if r.finish is not None and r.finish <= sv["t_end"]
          and r.max_new > 1]
    return 1e3 * p90(xs) if xs else None
