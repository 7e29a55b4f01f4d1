"""Metric arithmetic on synthetic records: every reader the benchmark
names, and the trace reduction."""
import types

import pytest

from lib import flops, spec, trace
from lib.serve import Burst, Insert, Rec

PEAKS = {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e12}
M = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
     "d_head": 16, "d_ff": 128, "vocab_size": 1000}


def _hist(count, total):
    return {"count": count, "sum": total}


def serve_ctx(kind="open_loop"):
    t0 = 100.0
    recs = {
        # arrived at 0.0, first token 0.5 s later, done at 1.5 (11 tokens)
        0: Rec(0, 10, 11, due=t0, submit=t0, insert_start=t0 + 0.2,
               first=t0 + 0.5, finish=t0 + 1.5),
        # arrived at 1.0, first token at 1.3, finishes after the close
        1: Rec(1, 10, 5, due=t0 + 1.0, submit=t0 + 1.0,
               insert_start=t0 + 1.1, first=t0 + 1.3, finish=t0 + 2.5),
        # arrived at 1.5, never started before the close at 2.0
        2: Rec(2, 10, 5, due=t0 + 1.5, submit=t0 + 1.5),
    }
    sv = {"t0": t0, "t_end": t0 + 2.0, "recs": recs,
          "inserts": [Insert(t0 + 0.2, t0 + 0.5, 16, 0),
                      Insert(t0 + 1.1, t0 + 1.3, 16, 1)],
          "bursts": [Burst(t0 + 0.5, t0 + 1.0, 4, 6, 100),
                     Burst(t0 + 1.3, t0 + 1.9, 4, 8, 200),
                     Burst(t0 + 1.9, t0 + 2.2, 4, 8, 200)],
          "snap0": {"histograms": {
              "serve.prefill_seconds": _hist(3, 1.0),
              "serve.decode_step_seconds": _hist(10, 0.5)}},
          "snap1": {"histograms": {
              "serve.prefill_seconds": _hist(5, 1.5),
              "serve.decode_step_seconds": _hist(18, 0.9)}}}
    return types.SimpleNamespace(
        serve=sv, train=None, trace=None, m=M, peaks=PEAKS, chips=1,
        window_s=2.0, setup_s=42.0,
        mix={"kind": kind, "serve": {"slots": 4}, "seq": 8})


def read(name, ctx):
    return spec.reader(name)(ctx)


def test_ttft_counts_from_the_scheduled_arrival_and_the_close():
    # 0.5, 0.3 and (close 2.0 - arrival 1.5) 0.5 -> p90 of [0.3, .5, .5]
    assert read("ttft_p90_ms", serve_ctx()) == pytest.approx(500.0)


def test_tpot_counts_requests_finished_in_the_window():
    # only request 0: (1.5 - 0.5) / 10
    assert read("tpot_p90_ms", serve_ctx()) == pytest.approx(100.0)


def test_queue_wait_counts_unstarted_requests_at_the_close():
    # 0.2, 0.1 and 0.5 -> p90 = 0.2 + 0.8 * (0.5 - 0.2)
    assert read("queue_wait_p90_ms", serve_ctx()) == pytest.approx(440.0)


def test_tokens_per_s_counts_deliveries_inside_the_window():
    ctx = serve_ctx("closed_backlog")
    # bursts ending in (t0, t_end]: 6 + 8 live steps; both insertions
    assert read("tokens_per_s", ctx) == pytest.approx((6 + 8 + 2) / 2.0)
    assert read("ttft_p90_ms", ctx) is None


def test_program_histograms_are_read_as_window_deltas():
    ctx = serve_ctx()
    assert read("insert_ms_mean", ctx) == pytest.approx(250.0)
    assert read("decode_step_ms.complete", ctx) == pytest.approx(50.0)
    assert read("decode_step_ms.batch-gen", ctx) == pytest.approx(50.0)


def test_slot_occupancy_and_shares_of_peak():
    ctx = serve_ctx("closed_backlog")
    # bursts inside the window: the first two (16 slot-steps each)
    assert read("slot_occupancy", ctx) == pytest.approx(100 * 14 / 32)
    f_ins = 2 * flops.insert_flops(M, 16)
    assert read("mfu.insert", ctx) == pytest.approx(
        100 * f_ins / (0.5 * PEAKS["bf16_flops"]))
    f_dec = 14 * flops.decode_flops(M, 0) + flops.attn_flops(M, 300)
    assert read("mfu.serve", ctx) == pytest.approx(
        100 * (f_dec + f_ins) / (2.0 * PEAKS["bf16_flops"]))


def test_device_readers_need_a_trace_and_peaks():
    ctx = serve_ctx("closed_backlog")
    for name in ("insert_roofline", "decode_roofline", "idle_share.serve"):
        assert read(name, ctx) is None
    ctx.trace = {"window_s": 2.0, "busy_s": 1.5,
                 "programs": {"prefill_into": 0.4, "burst": 1.0}}
    assert read("idle_share.serve", ctx) == pytest.approx(25.0)
    assert read("idle_share.train", ctx) == pytest.approx(25.0)
    by = 8 * flops.weight_bytes(M) + 300 * flops.kv_bytes_per_token(M)
    assert read("decode_roofline", ctx) == pytest.approx(
        100 * by / PEAKS["hbm_bytes_per_s"] / 1.0)
    ins = 2 * max(flops.insert_flops(M, 16) / PEAKS["bf16_flops"],
                  (flops.weight_bytes(M) + 16 * flops.kv_bytes_per_token(M))
                  / PEAKS["hbm_bytes_per_s"])
    assert read("insert_roofline", ctx) == pytest.approx(100 * ins / 0.4)
    ctx.peaks = None
    assert read("decode_roofline", ctx) is None


def test_training_readers():
    ctx = types.SimpleNamespace(
        serve=None, trace=None, m=M, peaks=PEAKS, chips=1, window_s=4.0,
        setup_s=9.0, mix={"kind": "train", "seq": 8},
        train={"steps": 10, "tokens": 10 * 4 * 8,
               "data_s": [0.001, 0.003]})
    assert read("train_tokens_per_s", ctx) == pytest.approx(80.0)
    assert read("data_ms_per_step", ctx) == pytest.approx(2.0)
    assert read("mfu.train", ctx) == pytest.approx(
        100 * 80.0 * flops.train_flops_per_token(M, 8) / PEAKS["bf16_flops"])
    assert read("setup_s", ctx) == 9.0


def test_flops_count_every_matmul_once():
    d, h, kv, dh, f, v = 64, 4, 2, 16, 128, 1000
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 2 * d * f
    assert flops.layer_params(M) == per_layer
    s = 10
    want = (2 * s * 2 * per_layer + 4 * 2 * h * dh * s * (s + 1) / 2
            + 2 * d * v)
    assert flops.insert_flops(M, s) == pytest.approx(want)
    assert flops.kv_bytes_per_token(M) == 2 * 2 * 2 * kv * dh


def test_trace_reduction_on_synthetic_intervals():
    tr = {"devices": {"/device:TPU:0": {
        "ops": [(1.0, 1.5, "fusion.1"), (1.4, 2.0, "fusion.2"),
                (3.0, 3.5, "fusion.1"), (9.0, 12.0, "outside")],
        "modules": [(1.0, 2.0, "jit_prefill_into(7)"),
                    (3.0, 3.5, "jit_burst(9)")]}},
        "host": [(0.5, 4.0, "bench.window"),
                 (2.0, 3.0, "bench.wait_arrival"),
                 (2.1, 2.9, "engine.insert"),
                 (3.5, 4.0, "bench.batcher_run")]}
    red = trace.reduce(tr)
    assert red["window_s"] == pytest.approx(3.5)
    assert red["busy_s"] == pytest.approx(1.5)
    assert red["programs"] == pytest.approx({"prefill_into": 1.0,
                                             "burst": 0.5})
    # ops are named by their program; one op name in two programs is two
    assert red["device_ops"] == [
        ["prefill_into/fusion.2", pytest.approx(0.6)],
        ["prefill_into/fusion.1", pytest.approx(0.5)],
        ["burst/fusion.1", pytest.approx(0.5)]]
    # the longest gap (2.0-3.0) is named after its innermost annotation
    assert red["idle_gaps"][0] == ["engine.insert", pytest.approx(1.0)]
    assert [g[0] for g in red["idle_gaps"]] == [
        "engine.insert", "host between start and bench.wait_arrival",
        "bench.batcher_run"]
