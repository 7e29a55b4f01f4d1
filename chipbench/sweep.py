"""Find the knee of an open-loop serving cell: serve its mix at several
fixed rates, one window each, in one process (one set-up).

    python chipbench/sweep.py --workload sc2-3b.complete --seed 7 \
        --seconds 20 --rates 3 4 5 6

For each rate it prints the requests that arrived, those finished inside
the window, the backlog when the window closed (arrived and not finished),
and the 90th percentiles of time to first token and time per output token.
The knee is the highest rate whose backlog stays small and whose finished
count keeps up with arrivals; the cell runs at about four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench.ROOT / "src"))
    sys.path.insert(0, str(bench.HERE))
    bench._prepare_jax()
    from lib import readers, spec, traffic
    from lib.serve import ServeCell

    w = spec.workload(args.workload)
    cfg = spec.config(w["config"])
    mix = spec.traffic(w["traffic"])
    bench.device_info(w["chips"], True)
    m = spec.model_overrides(cfg)
    cell = ServeCell(m, cfg["program"]["arch"], mix, args.seed)
    every = []
    for i, rate in enumerate(args.rates):
        mx = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        n = traffic.open_loop_count(mx, args.seconds)
        every += traffic.requests(mx, args.seed + i, n, m["vocab_size"],
                                  uid_base=i << 20)
    cell.warm(every)
    for i, rate in enumerate(args.rates):
        reqs = [r for r in every if r.uid >> 20 == i]
        cell.mix = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        win = cell.window(reqs, args.seconds)
        t0, t1 = win["t0"], win["t_end"]
        recs = list(win["recs"].values())
        fin = [r for r in recs if r.finish is not None and r.finish <= t1]
        ttft = [min(r.first if r.first is not None else t1, t1) - r.due
                for r in recs]
        tpot = [(r.finish - r.first) / (r.max_new - 1) for r in fin]
        print(json.dumps({
            "rate_per_s": rate, "arrived": len(recs),
            "finished_in_window": len(fin),
            "backlog_at_close": len(recs) - len(fin),
            "ttft_p90_ms": 1e3 * readers.p90(ttft),
            "tpot_p90_ms": 1e3 * readers.p90(tpot) if tpot else None,
            "window_s": t1 - t0}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except bench.NoChip as e:
        bench._log(str(e))
        sys.exit(3)
