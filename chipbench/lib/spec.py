"""Where the benchmark's data lives, found by the names in BENCHMARK.json.

Every configuration, traffic mix, limit set and metric sits in a file of
its own:

    chipbench/configs/<config>.json     published values, departures, sizes as run
    chipbench/traffic/<traffic>.json    one traffic mix's parameters
    chipbench/limits/<workload>.json    the correctness limits of one cell
    chipbench/metrics/<metric>.py       one metric's reader: read(ctx)
                                        (<quantity>.py for <quantity>.<part>)
    chipbench/peaks.json                chip peaks keyed by device_kind

so a later change adds a cell by adding files and entries, and edits none.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
from typing import Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parents[1]      # chipbench/
ROOT = HERE.parent                                      # the checkout


class SpecError(RuntimeError):
    """A name in BENCHMARK.json has no file, or a file disagrees."""


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


@functools.lru_cache(maxsize=None)
def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    for c in benchmark()["configs"]:
        if c["name"] == name:
            return _json(ROOT / c["file"])
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _json(HERE / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    return _json(HERE / "limits" / f"{workload_name}.json")


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"device_kind {device_kind!r} is not in "
                        f"chipbench/peaks.json; add its peaks with a source")
    return table[device_kind]


def _applies(metric: dict, cell: str, reported: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def end_to_end(cell: str) -> List[dict]:
    return [m for m in benchmark()["end_to_end"] if _applies(m, cell)]


def per_layer(cell: str) -> List[dict]:
    reported = {m["name"] for m in end_to_end(cell)}
    return [m for m in benchmark()["per_layer"]
            if _applies(m, cell, reported)]


@functools.lru_cache(maxsize=None)
def reader(metric: str):
    """The ``read(ctx)`` function of chipbench/metrics/<metric>.py, or,
    where that file is absent, of the quantity the metric splits:
    ``decode_step_ms.complete`` and ``decode_step_ms.batch-gen`` (one
    quantity moving different end-to-end metrics) share
    chipbench/metrics/decode_step_ms.py."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.is_file() and "." in metric:
        path = HERE / "metrics" / f"{metric.rsplit('.', 1)[0]}.py"
    if not path.is_file():
        raise SpecError(f"metric {metric!r} has no reader at "
                        f"chipbench/metrics/{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_overrides(cfg: dict) -> Dict:
    """The program's ModelConfig fields the configuration file states."""
    return dict(cfg["program"]["model"])
