"""Trainer: mean host time of the window's steps (train.host_seconds: a
step's wall time less its dispatch and its wait for the loss), in ms."""
from lib.program import last_observations


def read(ctx):
    xs = last_observations(ctx, "train.host_seconds")
    return None if xs is None else 1e3 * sum(xs) / len(xs)
