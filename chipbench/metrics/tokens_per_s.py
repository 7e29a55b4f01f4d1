"""Output tokens delivered to the host in the window (every live decode
step of the bursts that returned in it, and the first token of every
insertion that returned in it), over the window, in tokens/s."""


def read(ctx):
    sv = ctx.serve
    if sv is None or ctx.mix["kind"] != "closed_backlog":
        return None
    t0, t1 = sv["t0"], sv["t_end"]
    n = sum(b.live_steps for b in sv["bursts"] if t0 < b.end <= t1)
    n += sum(1 for i in sv["inserts"] if t0 < i.end <= t1)
    return n / (t1 - t0)
