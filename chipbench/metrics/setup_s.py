"""Set-up: process start to the window's first request or step, in s."""


def read(ctx):
    return ctx.setup_s
