"""Engine step: mean host time of one decode step over the window, from
the program's serve.decode_step_seconds histogram (a burst's time over
its steps), in ms."""
from lib.readers import hist_delta


def read(ctx):
    d = hist_delta(ctx, "serve.decode_step_seconds")
    return None if d is None else 1e3 * d[1] / d[0]
