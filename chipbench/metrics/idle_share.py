"""Device: share of the traced window in which no operation ran on the
chip, in %."""
from lib.readers import pct


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr["window_s"]:
        return None
    return pct(1.0 - tr["busy_s"] / tr["window_s"])
