"""Engine step: mean host time of one insertion over the window, from the
program's serve.prefill_seconds histogram, in ms."""
from lib.readers import hist_delta


def read(ctx):
    d = hist_delta(ctx, "serve.prefill_seconds")
    return None if d is None else 1e3 * d[1] / d[0]
