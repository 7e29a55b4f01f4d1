"""Reduce a profiler trace (``.xplane.pb``) to device busy time, each
program's device time, the top device operations and the longest idle
gaps, each gap named after the host annotation that covers it.

All intervals are clipped to the measured window, which the harness marks
with a host annotation of its own (``bench.window``).  Reading goes through
``jax.profiler.ProfileData`` only.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
Interval = Tuple[float, float, str]          # start_s, end_s, name


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, device_prefix: str = "/device:TPU:") -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]} as (start_s, end_s, name) intervals.

    A device plane's op line is "XLA Ops" and its program line "XLA
    Modules".  With ``device_prefix="/host:CPU"`` (the CPU backend, for
    tests) the ops are the host events that carry an ``hlo_op`` stat and
    the program is their ``hlo_module``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix) and \
                device_prefix != "/host:CPU":
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                            * 1e-9, e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                             * 1e-9, e.name) for e in line.events]
            devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name == "/host:CPU":
            cpu_ops, cpu_mods = [], []
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    if device_prefix == "/host:CPU":
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            cpu_ops.append(iv)
                            cpu_mods.append(iv[:2] + (str(st.get(
                                "hlo_module", "")),))
                            continue
                    host.append(iv)
            if device_prefix == "/host:CPU":
                devices["/host:CPU"] = {"ops": cpu_ops, "modules": cpu_mods}
    return {"devices": devices, "host": host}


def window_of(host: List[Interval]) -> Tuple[float, float]:
    marks = [iv for iv in host if iv[2] == WINDOW]
    if not marks:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return marks[-1][0], marks[-1][1]


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged (start, end) of ``intervals`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def program_name(module: str) -> str:
    """``jit_prefill_into(1234)`` -> ``prefill_into``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return re.sub(r"^jit_", "", name)


def op_name(op: str) -> str:
    """``%fusion.154 = bf16[...] fusion(...)`` -> ``fusion.154``."""
    return op.split(" = ", 1)[0].lstrip("%")


# ops that only contain others (a layer scan's loop): their time is their
# children's, so they are left out of the top operations
CONTAINERS = ("while", "conditional", "call")


def _program_of(mods, t: float) -> str:
    """The program whose interval holds time ``t`` (mods sorted)."""
    i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
    if i >= 0 and mods[i][0] <= t <= mods[i][1]:
        return program_name(mods[i][2])
    return "?"


def _gap_label(annots, s: float, e: float) -> str:
    """The innermost host annotation over the gap's middle; where none
    covers it (an enclosing one still open when the trace stopped is not
    recorded), the annotations that end before and begin after it."""
    mid = 0.5 * (s + e)
    cover = [a for a in annots if a[0] <= mid <= a[1]]
    if cover:
        return min(cover, key=lambda a: a[1] - a[0])[2]
    before = [a for a in annots if a[1] <= mid]
    after = [a for a in annots if a[0] >= mid]
    prev = max(before, key=lambda a: a[1])[2] if before else "start"
    nxt = min(after, key=lambda a: a[0])[2] if after else "end"
    return f"host between {prev} and {nxt}"


def reduce(tr: dict, top: int = 10) -> dict:
    lo, hi = window_of(tr["host"])
    window_s = hi - lo
    busy, programs, ops = [], collections.Counter(), collections.Counter()
    first_gaps: Optional[List[Tuple[float, float]]] = None
    for name in sorted(tr["devices"]):
        dev = tr["devices"][name]
        merged = union(dev["ops"] or dev["modules"], lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for s, e, n in dev["modules"]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                programs[program_name(n)] += d
        mods = sorted(dev["modules"])
        for s, e, n in dev["ops"]:
            d = min(e, hi) - max(s, lo)
            n = op_name(n)
            if d > 0 and not n.split(".")[0] in CONTAINERS:
                ops[f"{_program_of(mods, s)}/{n}"] += d
        if first_gaps is None:
            edges = [lo] + [x for se in merged for x in se] + [hi]
            first_gaps = [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
    n_dev = max(1, len(busy))
    annots = [iv for iv in tr["host"] if iv[2] != WINDOW
              and (iv[2].startswith(("engine.", "trainer.", "bench.")))]
    gaps = []
    for s, e in sorted(first_gaps or [], key=lambda g: g[0] - g[1])[:top]:
        gaps.append([_gap_label(annots, s, e), e - s])
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "programs": {k: v / n_dev for k, v in programs.items()},
        "device_ops": [[n, v / n_dev] for n, v in ops.most_common(top)],
        "idle_gaps": gaps,
    }
