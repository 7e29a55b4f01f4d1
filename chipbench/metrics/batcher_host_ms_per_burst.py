"""Scheduler: the batcher's own host time per decode burst over the window
(serve.batcher_host_seconds: its phases outside every Engine call, over
the bursts serve.decode_step_seconds counts), in ms."""
from lib.readers import hist_delta


def read(ctx):
    host = hist_delta(ctx, "serve.batcher_host_seconds")
    bursts = hist_delta(ctx, "serve.decode_step_seconds")
    if host is None or bursts is None:
        return None
    return 1e3 * host[1] / bursts[0]
