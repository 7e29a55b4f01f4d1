"""Trainer: mean host time of the data source's batch() per window step,
in ms."""


def read(ctx):
    if ctx.train is None or not ctx.train["data_s"]:
        return None
    xs = ctx.train["data_s"]
    return 1e3 * sum(xs) / len(xs)
