"""Arithmetic the metric readers (chipbench/metrics/*.py) share.

A reader gets the run's context ``ctx`` and returns a number, or None when
the run holds nothing for it to read.
"""
from __future__ import annotations

import numpy as np

from . import flops


def p90(xs) -> float:
    """The 90th percentile (numpy's linear interpolation)."""
    return float(np.percentile(np.asarray(xs, np.float64), 90))


def hist_delta(ctx, name: str):
    """(count, sum) a program histogram gained over the window."""
    if ctx.serve is None:
        return None
    a = ctx.serve["snap0"]["histograms"].get(name, {"count": 0, "sum": 0.0})
    b = ctx.serve["snap1"]["histograms"].get(name, {"count": 0, "sum": 0.0})
    n, s = b["count"] - a["count"], b["sum"] - a["sum"]
    return (n, s) if n > 0 else None


def window_inserts(ctx):
    """Insertions that began and ended inside the window."""
    sv = ctx.serve
    return [i for i in sv["inserts"] if i.start >= sv["t0"]
            and i.end <= sv["t_end"]]


def window_bursts(ctx):
    """Decode bursts that began and ended inside the window."""
    sv = ctx.serve
    return [b for b in sv["bursts"] if b.start >= sv["t0"]
            and b.end <= sv["t_end"]]


def insert_work(ctx):
    """(model FLOPs, bytes) of the window's insertions at their buckets:
    the weights read once and the bucket's K/V written per insertion."""
    ins = window_inserts(ctx)
    if not ins:
        return None
    m = ctx.m
    f = sum(flops.insert_flops(m, i.bucket) for i in ins)
    b = sum(flops.weight_bytes(m) + i.bucket * flops.kv_bytes_per_token(m)
            for i in ins)
    return f, b


def decode_work(ctx, bursts):
    """(model FLOPs, bytes) of ``bursts``: every live step's token, the
    weights read once per step and the K/V of the live positions."""
    m = ctx.m
    steps = sum(b.k for b in bursts)
    tokens = sum(b.live_steps for b in bursts)
    ctxs = sum(b.kv_positions for b in bursts)
    f = (tokens * (flops.decode_flops(m, 0)) + flops.attn_flops(m, ctxs))
    by = steps * flops.weight_bytes(m) + ctxs * flops.kv_bytes_per_token(m)
    return f, by


def pct(x: float) -> float:
    return 100.0 * x
