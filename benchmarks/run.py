"""Benchmark entrypoint: one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows (derived = the table's
headline number) and writes a schema-stable JSON report consumable by
``benchmarks.compare``:

    {"schema_version": 1, "profile": "smoke|fast|full",
     "kernels": [...], "tables": {"table1": [...], ...},
     "serve_throughput": {...},
     "fig1": {...}|null, "roofline_summary": {...}|null,
     "obs": <repro.obs registry snapshot>}

``--params-cache DIR`` caches trained classifier params on disk keyed by
a content hash of the training config, so repeat runs (CI) skip the
training loops entirely.

Profiles: ``full`` = paper-scale task counts/seeds; ``fast`` (default)
completes on CPU in minutes; ``smoke`` is the CI budget (~1-2 min) —
schema-identical, numbers undertrained/noisy by design."""
from __future__ import annotations

import argparse
import json
import time

from repro import obs
from repro.launch.compile_cache import enable_compile_cache

SCHEMA_VERSION = 1


def _csv(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def _mean_reduction(table):
    red = [row["flops_reduction"] for r in table for row in r["rows"][1:]]
    return sum(red) / max(len(red), 1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", choices=("smoke", "fast", "full"),
                    default="fast")
    ap.add_argument("--full", action="store_true",
                    help="legacy alias for --profile full")
    ap.add_argument("--json-out", default="bench_results.json")
    ap.add_argument("--params-cache", default=None, metavar="DIR",
                    help="cache trained table params here (content-hash "
                         "keyed); repeat runs skip training")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON span timeline "
                         "here (enables span tracing for the run)")
    args = ap.parse_args()
    enable_compile_cache()
    profile = "full" if args.full else args.profile
    fast = profile != "full"
    smoke = profile == "smoke"

    if args.trace_out:
        obs.enable_tracing(True)
    reg = obs.Registry()
    tables = {}
    fig1 = None
    roofline_summary = None
    with obs.scoped(reg), obs.span("benchmarks.run"):
        from . import kernel_bench
        with obs.span("kernel_bench", cat="bench", track="bench"):
            kb = kernel_bench.run(fast=fast)
        for r in kb:
            _csv(r["name"], r["us_per_call"],
                 r.get("flops_reduction", r.get("colmax_overhead", "")))

        from . import table1_bert, table2_distilbert, table3_longformer
        for name, mod in (("table1", table1_bert),
                          ("table2", table2_distilbert),
                          ("table3", table3_longformer)):
            t0 = time.time()
            with obs.span(name, cat="bench", track="bench"):
                tab = mod.run(fast=fast, smoke=smoke,
                              cache_dir=args.params_cache)
            wall = time.time() - t0
            tables[name] = tab
            reg.histogram(f"bench.{name}.wall_seconds").observe(wall)
            _csv(f"{name}_mca", wall * 1e6 / max(len(tab), 1),
                 f"mean_flops_reduction={_mean_reduction(tab):.2f}x")

        from . import serve_throughput as serve_mod
        t0 = time.time()
        with obs.span("serve_throughput", cat="bench", track="bench"):
            serve_tp = serve_mod.run(fast=fast, smoke=smoke)
        for row in serve_tp["rows"]:
            _csv(f"serve_{row['batcher']}", (time.time() - t0) * 1e6 / 2,
                 f"tokens_per_s={row['tokens_per_s']:.0f};"
                 f"prefill_ratio={row['prefill_flops_ratio']:.2f}x;"
                 f"parity={row['parity_ok']}")

        if not smoke:
            from . import fig1_tradeoff
            t0 = time.time()
            fig1 = fig1_tradeoff.run(fast=fast)
            knee = min((row for row in fig1["bert"]["rows"][1:]),
                       key=lambda r: abs(r["acc"]
                                         - fig1["bert"]["baseline_acc"]
                                         + 0.01))
            _csv("fig1_tradeoff", (time.time() - t0) * 1e6 / 8,
                 f"knee_alpha={knee['alpha']};"
                 f"knee_flops={knee['flops_reduction']:.2f}x")

        # roofline summary from the dry-run cache (if present)
        try:
            from . import roofline
            rows = roofline.load_results()
            if rows:
                roofline_summary = roofline.summary(rows)
                _csv("roofline_dryrun", 0.0,
                     f"cells={roofline_summary['cells']};"
                     f"compiled={roofline_summary['compiled']};"
                     f"fits={roofline_summary['fits_hbm']}")
        except Exception:                                 # noqa: BLE001
            pass

    out = {
        "schema_version": SCHEMA_VERSION,
        "profile": profile,
        "kernels": kb,
        "tables": tables,
        "serve_throughput": serve_tp,
        "fig1": fig1,
        "roofline_summary": roofline_summary,
        "obs": reg.snapshot(),
    }
    with open(args.json_out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    print(f"wrote {args.json_out} (profile={profile})")
    if args.trace_out:
        trace = obs.export_chrome_trace(args.trace_out, registry=reg)
        obs.enable_tracing(False)
        print(f"wrote {args.trace_out} "
              f"({len(trace['traceEvents'])} trace events)")


if __name__ == "__main__":
    main()
