"""Scheduler: 90th percentile of the window's insertions' queue wait on the
program's own clock (serve.queue_seconds: submit to insertion start), in
ms."""
from lib.program import window_observations
from lib.readers import p90


def read(ctx):
    xs = window_observations(ctx, "serve.queue_seconds")
    return None if xs is None else 1e3 * p90(xs)
