"""Readings that set a cell's correctness limits: the program's numbers
over many seeds, and the control's (the plain reference computed in
float8 in the program's place) over some of them, in one process.

    python chipbench/readings.py --workload sc2-3b.complete --seconds 8 \
        --seeds 11 12 13 --control-seeds 11 12

Each seed runs the cell as ``run.py`` does (its own weights, the mix's
load, a window of ``--seconds``); the numbers compared and the control's
are printed per seed, and their largest (program) and smallest (control)
as the last line, in JSON.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench


def mca_on(workload: str) -> dict:
    """The cell's mix keys with MCA switched on."""
    sys.path.insert(0, str(bench.HERE))
    from lib import spec
    mix = spec.traffic(spec.workload(workload)["traffic"])
    if "serve" in mix:
        return {"serve": dict(mix["serve"], mca=True)}
    return {"mca": True}


def peaked(scale: float) -> None:
    """Scale every query and key projection the benchmark draws by
    ``scale`` (the attention logits by its square), for the program and
    the reference alike: sharper attention than the default draw gives."""
    sys.path.insert(0, str(bench.HERE))
    from lib import weights
    leaf = weights._leaf

    def scaled(key, path, shape, dtype):
        x = leaf(key, path, shape, dtype)
        if path.endswith("['wq']") or path.endswith("['wk']"):
            x = (x.astype("float32") * scale).astype(dtype)
        return x
    weights._leaf = scaled


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--mca", action="store_true",
                    help="turn MCA on (alpha 0.2 on v_proj) in the cell's "
                         "serving stack or trainer, for a look at what it "
                         "does to the numbers compared")
    ap.add_argument("--qk-scale", type=float, default=1.0,
                    help="scale the drawn query and key projections (a "
                         "look at peaked attention; see peaked())")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench.ROOT / "src"))
    bench._prepare_jax()
    if args.qk_scale != 1.0:
        peaked(args.qk_scale)
    prog, ctrl = {}, {}
    for seed in args.seeds:
        r = bench.run(args.workload, seed, args.seconds, False,
                      control=seed in args.control_seeds,
                      t_start=time.perf_counter(),
                      mix_override=mca_on(args.workload) if args.mca
                      else None)
        line = {"seed": seed, "correct": r["correct"],
                "failed": r["failed"],
                "program": {k: c["value"] for k, c in r["checks"].items()},
                "control": r.get("control"), "faults": r.get("faults")}
        print(json.dumps(line), flush=True)
        for k, v in line["program"].items():
            prog[k] = max(prog.get(k, 0.0), v)
        for k, v in (line["control"] or {}).items():
            ctrl[k] = min(ctrl.get(k, float("inf")), v)
    print(json.dumps({"workload": args.workload, "mca": args.mca,
                      "qk_scale": args.qk_scale, "seeds": args.seeds,
                      "control_seeds": args.control_seeds,
                      "program_max": prog, "control_min": ctrl}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except bench.NoChip as e:
        bench._log(str(e))
        sys.exit(3)
