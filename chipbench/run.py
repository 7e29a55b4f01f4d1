"""Run one cell of the benchmark on the chip this process finds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Reads the cell from BENCHMARK.json and its files (chipbench/configs,
traffic, limits, metrics), sets it up (weights from the seed, the program's
server or trainer, every program it runs compiled or loaded from the
persistent cache at <checkout>/.jax_cache), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and
prints one JSON line last on stdout:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and reports its per-layer metrics, the
device's busy time and the breakdown.  Each number compared for
``correct`` is printed beside its limit as the last lines on stderr and
under ``checks``.  Exits non-zero with no result when JAX finds no TPU or
fewer chips than the cell needs, or when the program is not in the
checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"


class NoChip(RuntimeError):
    """The process found no TPU, or fewer chips than the cell needs."""


def _log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def _prepare_jax() -> None:
    """The persistent compile cache at the checkout's fixed path, set
    before JAX is imported so the program's own setting agrees."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """Compilations (or cache loads) JAX reports, from its own events."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s), found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peak_memory(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class Tracer:
    """The profiler over the window, and the window's own annotation."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = OUT_DIR / "trace"
        self.ann = None

    def open(self):
        if not self.on:
            return
        import jax
        from jax.profiler import TraceAnnotation
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.dir))
        self.ann = TraceAnnotation("bench.window")
        self.ann.__enter__()

    def close(self):
        if self.ann is None:
            return
        import jax
        self.ann.__exit__(None, None, None)
        self.ann = None
        jax.profiler.stop_trace()

    def reduce(self, device_prefix: str) -> dict:
        from lib import trace
        red = trace.reduce(trace.load(trace.find_xplane(str(self.dir)),
                                      device_prefix))
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


# ----------------------------------------------------------------- cells
def run_serve(ctx, cell, reqs, tracer, compiles, control):
    from lib import check
    n0 = compiles.n
    win = cell.window(reqs, ctx.seconds, tracer.open, tracer.close)
    ctx.compiles_in_window = compiles.n - n0
    ctx.serve = win
    ctx.window_s = win["t_end"] - win["t0"]
    ctx.memory_peak_bytes = peak_memory(ctx.chips)
    ok = {u for u, s in win["status"].items() if s == "ok"}
    recs = list(win["recs"].values())
    late = [r.submit - r.due for r in recs] or [0.0]
    ctx.lateness_max_ms = 1e3 * max(late)
    ctx.lateness_mean_ms = 1e3 * sum(late) / len(late)
    # a closed backlog's requests still queued at the close never started
    recs = [r for r in recs if r.insert_start is not None
            or ctx.mix["kind"] == "open_loop"]
    ctx.attempted = len(recs)
    ctx.failed = sum(1 for r in recs if r.uid not in ok)
    done = win["done"]
    picked = check.sample([r for r in recs if r.uid in ok],
                          ctx.mix["check"]["requests"], ctx.seed)
    prompts = {r.uid: r.prompt for r in reqs}
    items = [(prompts[r.uid], done[r.uid]) for r in picked]
    abstract = cell.abstract
    # free the program's state before the reference runs
    del cell.engine, cell.batcher, cell.params, win
    gc.collect()
    precs = ("f32", "fp8") if control else ("f32",)
    gaps = check.serve_gaps(ctx.ref, ctx.m, abstract, ctx.seed, items,
                            ctx.mix, precs)
    info = {"tokens_compared": gaps["tokens"]}
    if control:
        info["control"] = {"widest_gap": gaps["control.fp8"]}
    return {"widest_gap": gaps["served"]}, info


def run_train(ctx, cell, tracer, compiles, control):
    from lib import check
    n0 = compiles.n
    win = cell.window(ctx.seconds, tracer.open, tracer.close)
    ctx.compiles_in_window = compiles.n - n0
    ctx.train = win
    ctx.window_s = win["t_end"] - win["t0"]
    ctx.memory_peak_bytes = peak_memory(ctx.chips)
    ctx.attempted = win["steps"]
    ctx.failed = sum(1 for s in win["statuses"] if s != "ok")
    prog = (cell.losses, cell.grad_norms, cell.change_norms)
    batches, abstract = cell.rows.kept, cell.abstract
    del cell.trainer
    gc.collect()
    ref = check.train_reference(ctx.ref, ctx.m, abstract, ctx.seed,
                                batches, cell.opt)
    info = {}
    if control:
        low = check.train_reference(ctx.ref, ctx.m, abstract, ctx.seed,
                                    batches, cell.opt, "fp8")
        info["control"] = check.train_gaps(low, ref)
        # the fault "half of the batch left out, the mean taken over the
        # rest", planted in the reference put in the program's place
        half = [{k: v[:len(v) // 2] for k, v in b.items()} for b in batches]
        info["faults"] = {"half_batch": check.train_gaps(
            check.train_reference(ctx.ref, ctx.m, abstract, ctx.seed, half,
                                  cell.opt), ref)}
    return check.train_gaps(prog, ref), info


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, config_override=None,
        mix_override=None, control: bool = False,
        t_start: float = T_START, limits_override=None) -> dict:
    """One run of a cell; returns the result object (see module doc).
    The overrides replace the configuration's program dict and keys of the
    mix (tests run a tiny copy of a cell on the CPU).  ``control`` also
    reads the correctness control's numbers (the reference in float8 in
    the program's place) into ``result["control"]`` and, in a training
    cell, those of a planted fault (half of each batch left out) into
    ``result["faults"]``; benchmark runs never do."""
    sys.path.insert(0, str(HERE))
    import importlib

    from lib import spec, traffic

    w = spec.workload(workload)
    cfg = spec.config(w["config"])
    mix = dict(spec.traffic(w["traffic"]), **(mix_override or {}))
    limits = limits_override or spec.limits(workload)
    ctx = types.SimpleNamespace(
        cell=workload, seed=seed, seconds=seconds, chips=w["chips"],
        config=cfg, mix=mix, m=dict(config_override or
                                    spec.model_overrides(cfg)),
        serve=None, train=None, trace=None, setup_s=None,
        ref=importlib.import_module(f"lib.{cfg['reference']}"))
    device = device_info(w["chips"], require_chip)
    ctx.peaks = spec.peaks(device["kind"]) if require_chip else None
    compiles = CompileCount()
    tracer = Tracer(trace)
    arch = cfg["program"]["arch"]

    if mix["kind"] == "train":
        from lib.train import TrainCell
        cell = TrainCell(ctx.m, arch, mix, seed)
        ctx.setup_s = time.perf_counter() - t_start
        numbers, info = run_train(ctx, cell, tracer, compiles, control)
    else:
        from lib.serve import ServeCell
        cell = ServeCell(ctx.m, arch, mix, seed)
        if mix["kind"] == "open_loop":
            n = traffic.open_loop_count(mix, seconds)
        else:
            n = mix["pool"]
        reqs = traffic.requests(mix, seed, n, ctx.m["vocab_size"])
        cell.warm(reqs)
        ctx.setup_s = time.perf_counter() - t_start
        numbers, info = run_serve(ctx, cell, reqs, tracer, compiles,
                                  control)

    if trace:
        ctx.trace = tracer.reduce("/device:TPU:" if require_chip
                                  else "/host:CPU")
    metrics = {}
    wanted = spec.per_layer(workload) if trace else spec.end_to_end(workload)
    for m in wanted:
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = {k: {"value": v, "limit": limits[k]["limit"]}
              for k, v in numbers.items()}
    correct = (ctx.failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    result = {"correct": bool(correct), "attempted": ctx.attempted,
              "failed": ctx.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    if control:
        result["control"] = info.pop("control")
        if "faults" in info:
            result["faults"] = info.pop("faults")
    result["checks"] = checks
    _log(f"{workload} seed {seed}: setup_s {ctx.setup_s:.3f}, window "
         f"{ctx.window_s:.3f}s, attempted {ctx.attempted}, failed "
         f"{ctx.failed}, compiles in window {ctx.compiles_in_window}, "
         f"{json.dumps(info)}")
    if ctx.compiles_in_window:
        _log(f"warning: {ctx.compiles_in_window} compilation(s) inside the "
             "measured window")
    if ctx.serve is not None:
        _log(f"generator lateness: max {ctx.lateness_max_ms:.3f} ms, mean "
             f"{ctx.lateness_mean_ms:.3f} ms")
    for k, c in checks.items():
        _log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        _log(f"the program (src/repro) is not in {ROOT}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    _prepare_jax()
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        _log(str(e))
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
