"""The readers of the program's own spans and histograms: on synthetic
snapshots with a filled registry, and in a traced run of each tiny cell."""
import types

import pytest

import tiny
from lib import spec
from repro import obs

NEW = {
    "sc2-3b.complete": ("batcher_host_ms_per_burst.complete",
                        "sched_wait_p90_ms", "inter_token_p90_ms"),
    "sc2-3b.batch-gen": ("batcher_host_ms_per_burst.batch-gen",),
    "bert-base.train": ("trainer_host_ms_per_step", "dispatch_ms_per_step"),
}


def _hist(count, total):
    return {"count": count, "sum": total}


def serve_ctx(snap0, snap1):
    return types.SimpleNamespace(serve={"snap0": {"histograms": snap0},
                                        "snap1": {"histograms": snap1}},
                                 train=None)


def read(name, ctx):
    return spec.reader(name)(ctx)


@pytest.mark.parametrize("name", ["batcher_host_ms_per_burst.complete",
                                  "batcher_host_ms_per_burst.batch-gen"])
def test_batcher_host_time_is_read_per_burst_of_the_window(name):
    ctx = serve_ctx(
        {"serve.batcher_host_seconds": _hist(30, 0.1),
         "serve.decode_step_seconds": _hist(10, 0.5)},
        {"serve.batcher_host_seconds": _hist(54, 0.3),
         "serve.decode_step_seconds": _hist(18, 0.9)})
    # 0.2 s of batcher host time over 8 bursts
    assert read(name, ctx) == pytest.approx(25.0)
    # a program that keeps no such histogram reports nothing
    bare = serve_ctx({"serve.decode_step_seconds": _hist(10, 0.5)},
                     {"serve.decode_step_seconds": _hist(18, 0.9)})
    assert read(name, bare) is None


@pytest.mark.parametrize("name,hist", [
    ("sched_wait_p90_ms", "serve.queue_seconds"),
    ("inter_token_p90_ms", "serve.inter_token_seconds")])
def test_request_percentiles_take_exactly_the_window(name, hist):
    with obs.scoped() as reg:
        h = reg.histogram(hist)
        h.observe(50.0)                       # before the window
        for v in range(1, 11):
            h.observe(v / 1000)               # the window: 1..10 ms
        h.observe(60.0)                       # after the close
        ctx = serve_ctx({hist: _hist(1, 50.0)},
                        {hist: _hist(11, 50.055)})
        # numpy's p90 of 1..10
        assert read(name, ctx) == pytest.approx(9.1)
        assert read(name, serve_ctx({}, {})) is None
        assert read(name, types.SimpleNamespace(serve=None,
                                                train=None)) is None
    with obs.scoped() as reg:
        h = reg.histogram(hist)
        for _ in range(1100):                 # the reservoir halves
            h.observe(0.001)
        ctx = serve_ctx({hist: _hist(0, 0.0)}, {hist: _hist(1100, 1.1)})
        assert read(name, ctx) is None


@pytest.mark.parametrize("name,hist", [
    ("trainer_host_ms_per_step", "train.host_seconds"),
    ("dispatch_ms_per_step", "train.dispatch_seconds")])
def test_training_means_take_the_last_steps(name, hist):
    ctx = types.SimpleNamespace(serve=None, train={"steps": 3})
    with obs.scoped() as reg:
        assert read(name, ctx) is None        # no such histogram
        for v in (0.5, 0.001, 0.002, 0.003):
            reg.histogram(hist).observe(v)
        assert read(name, ctx) == pytest.approx(2.0)
        # the reservoir holds fewer than the window's steps: all of them
        many = types.SimpleNamespace(serve=None, train={"steps": 2000})
        assert read(name, many) == pytest.approx(126.5)
        assert read(name, types.SimpleNamespace(serve=None,
                                                train=None)) is None


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_tiny_run_reports_the_program_metrics(workload):
    r = tiny.run(workload, seed=2 ** 31 + 7, trace=True)
    for name in NEW[workload]:
        assert r["metrics"][name]["value"] >= 0, (name, r["metrics"])


def test_a_program_without_window_reads_reports_nothing(monkeypatch):
    """An older program keeps histograms that cannot give back single
    observations: every reader of them reports nothing, and none raises."""
    monkeypatch.delattr(obs.Histogram, "between")
    with obs.scoped() as reg:
        for hist in ("serve.queue_seconds", "train.host_seconds"):
            reg.histogram(hist).observe(0.001)
        ctx = serve_ctx({"serve.queue_seconds": _hist(0, 0.0)},
                        {"serve.queue_seconds": _hist(1, 0.001)})
        assert read("sched_wait_p90_ms", ctx) is None
        train = types.SimpleNamespace(serve=None, train={"steps": 1})
        assert read("trainer_host_ms_per_step", train) is None
