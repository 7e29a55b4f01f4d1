"""The measurement path refuses to run without a chip or without the
program, and prints no result then."""
import os
import shutil
import subprocess
import sys

from lib import spec

ROOT = spec.ROOT


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "sc2-3b.complete",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""


def test_every_name_in_the_benchmark_has_its_files():
    b = spec.benchmark()
    for w in b["workloads"]:
        spec.config(w["config"])
        spec.traffic(w["traffic"])
        spec.limits(w["name"])
        assert spec.end_to_end(w["name"]) and spec.per_layer(w["name"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(spec.reader(m["name"]))
    spec.peaks("TPU v5 lite")


def test_benchmark_json_keeps_to_its_contract():
    import re
    b = spec.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(name.match(n) for n in names)
    assert len(set(x["name"] for x in b["end_to_end"] + b["per_layer"])) \
        == len(b["end_to_end"]) + len(b["per_layer"])
    assert 1 <= b["run_seconds"] <= 51
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and unit.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and unit.match(m["unit"])
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in b["workloads"]:
        mine = spec.end_to_end(w["name"])
        assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
