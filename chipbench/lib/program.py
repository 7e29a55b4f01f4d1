"""Observations the program keeps in its own histograms, read back for the
window: the metric readers of the program's spans share these.

Each returns None where the program keeps no such histogram, or its
histograms cannot give back single observations, so a reader of a metric
that a program lacks reports nothing.
"""
from __future__ import annotations


def _live(name: str):
    """The program's histogram ``name`` in the live registry, or None."""
    from repro import obs
    reg = obs.get_registry()
    if name not in reg.snapshot(include_device=False)["histograms"]:
        return None
    h = reg.histogram(name)
    return h if hasattr(h, "between") else None


def window_observations(ctx, name: str):
    """A serving window's observations of ``name``: those whose running
    index lies between the counts of the window's two snapshots."""
    if ctx.serve is None:
        return None
    a = ctx.serve["snap0"]["histograms"].get(name)
    b = ctx.serve["snap1"]["histograms"].get(name)
    h = _live(name)
    if b is None or h is None:
        return None
    xs = h.between(int(a["count"]) if a else 0, int(b["count"]))
    return xs or None


def last_observations(ctx, name: str):
    """A training window's observations of ``name``: the last
    ``ctx.train["steps"]``, since the window's steps are the last the
    trainer ran (all the reservoir holds, where it holds fewer)."""
    if ctx.train is None or not ctx.train["steps"]:
        return None
    h = _live(name)
    return (h.last(ctx.train["steps"]) or None) if h is not None else None
