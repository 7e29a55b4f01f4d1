"""Scheduler: 90th percentile over the requests the program finished in the
window of (finish - first token) / (tokens - 1), from its own
serve.inter_token_seconds, in ms."""
from lib.program import window_observations
from lib.readers import p90


def read(ctx):
    xs = window_observations(ctx, "serve.inter_token_seconds")
    return None if xs is None else 1e3 * p90(xs)
