"""Tokens of the steps trained in the window, over the window, in
tokens/s."""


def read(ctx):
    if ctx.train is None:
        return None
    return ctx.train["tokens"] / ctx.window_s
