"""Trainer: mean time of the window's steps to dispatch the step program
(train.dispatch_seconds: the train_step call until it returns), in ms."""
from lib.program import last_observations


def read(ctx):
    xs = last_observations(ctx, "train.dispatch_seconds")
    return None if xs is None else 1e3 * sum(xs) / len(xs)
