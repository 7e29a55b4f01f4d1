"""insert_kernel_share: the share of a window's insertions whose program
ran attention on the flash kernel, read from the program's counters."""
import types

import pytest

import tiny
from lib import spec

NAMES = ("insert_kernel_share.complete", "insert_kernel_share.batch-gen")


def serve_ctx(c0, c1):
    return types.SimpleNamespace(
        serve={"snap0": {"counters": c0, "histograms": {}},
               "snap1": {"counters": c1, "histograms": {}}},
        train=None)


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_window_delta(name):
    read = spec.reader(name)
    ctx = serve_ctx({"serve.insertions": 10,
                     "serve.insert_kernel_insertions": 2},
                    {"serve.insertions": 30,
                     "serve.insert_kernel_insertions": 17})
    assert read(ctx) == pytest.approx(75.0)          # 15 of 20
    # the counter first appears inside the window
    assert read(serve_ctx({"serve.insertions": 4},
                          {"serve.insertions": 8,
                           "serve.insert_kernel_insertions": 4})) == 100.0
    # a program without the counter, or a window without insertions
    assert read(serve_ctx({"serve.insertions": 1},
                          {"serve.insertions": 9})) is None
    assert read(serve_ctx({"serve.insertions": 3,
                           "serve.insert_kernel_insertions": 3},
                          {"serve.insertions": 3,
                           "serve.insert_kernel_insertions": 3})) is None
    assert read(types.SimpleNamespace(serve=None, train=None)) is None


def test_traced_tiny_run_reports_the_share():
    """A tiny completion cell whose heads are whole lanes and whose
    prompts pad to buckets of 128 and 256 inserts on the kernel only."""
    import run as bench
    mix = dict(tiny.SERVE["sc2-3b.complete"],
               prompt_len={"dist": "uniform", "min": 90, "max": 200},
               serve={"slots": 2, "max_len": 288, "check_every": 4,
                      "mca": False},
               check={"requests": 1})
    r = bench.run("sc2-3b.complete", 2 ** 31 + 11, 1.5, trace=True,
                  require_chip=False,
                  config_override=dict(tiny.DECODER, d_head=128),
                  mix_override=mix)
    assert r["metrics"]["insert_kernel_share.complete"]["value"] == 100.0
