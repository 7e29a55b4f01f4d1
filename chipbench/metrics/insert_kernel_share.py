"""Engine step: share of the window's insertions whose program ran its
attention on the flash kernel, from the program's counters (window delta
of serve.insert_kernel_insertions over that of serve.insertions), in %.
A program that keeps no such counter reports nothing."""
from lib.readers import pct

KERNEL, ALL = "serve.insert_kernel_insertions", "serve.insertions"


def read(ctx):
    if ctx.serve is None:
        return None
    c0 = ctx.serve["snap0"].get("counters", {})
    c1 = ctx.serve["snap1"].get("counters", {})
    if KERNEL not in c1:
        return None
    n = c1.get(ALL, 0) - c0.get(ALL, 0)
    return pct((c1[KERNEL] - c0.get(KERNEL, 0)) / n) if n > 0 else None
