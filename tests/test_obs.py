"""Unit tests for the repro.obs metrics layer (registry, scoping, sink)."""
import json
import math
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = obs.Registry()
        reg.counter("c").inc()
        reg.counter("c").inc(2.5)
        assert reg.counter("c").value == 3.5
        reg.gauge("g").set(7)
        assert reg.gauge("g").value == 7.0
        h = reg.histogram("h")
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        assert h.count == 4
        assert h.total == 10.0
        assert h.min == 1.0 and h.max == 4.0
        assert h.mean == 2.5

    def test_jax_scalars_coerced(self):
        reg = obs.Registry()
        reg.counter("c").inc(jnp.asarray(2.0))
        reg.gauge("g").set(np.float32(1.5))
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 2.0
        assert snap["gauges"]["g"] == 1.5
        json.dumps(snap)                       # fully serializable

    def test_timer_records_elapsed(self):
        reg = obs.Registry()
        with reg.timer("t"):
            pass
        h = reg.histogram("t")
        assert h.count == 1
        assert 0.0 <= h.total < 1.0

    def test_histogram_percentiles(self):
        h = obs.Histogram()
        for v in range(100):
            h.observe(float(v))
        assert abs(h.percentile(50) - 50.0) <= 2.0
        assert h.percentile(95) >= 90.0
        s = h.summary()
        assert s["count"] == 100 and not math.isnan(s["p50"])

    def test_snapshot_empty_registry(self):
        snap = obs.Registry().snapshot()
        assert snap == {"counters": {}, "gauges": {}, "histograms": {}}


class TestScoping:
    def test_scoped_isolates_from_global(self):
        g = obs.get_registry()
        before = g.counter("scope.test").value
        with obs.scoped() as reg:
            assert obs.get_registry() is reg
            obs.get_registry().counter("scope.test").inc()
            assert reg.counter("scope.test").value == 1
        assert g.counter("scope.test").value == before
        assert obs.get_registry() is g

    def test_scoped_nesting(self):
        with obs.scoped() as outer:
            with obs.scoped() as inner:
                obs.get_registry().counter("n").inc()
            assert inner.counter("n").value == 1
            assert outer.counter("n").value == 0

    def test_scopes_are_thread_local(self):
        seen = {}

        def worker():
            seen["reg"] = obs.get_registry()

        with obs.scoped() as reg:
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen["reg"] is not reg      # other thread saw the global


class TestSink:
    def test_write_and_read(self, tmp_path):
        sink = obs.JsonlSink(str(tmp_path / "m.jsonl"))
        sink.write("train_step", step=1, loss=2.5,
                   tier_hist=jnp.asarray([1.0, 2.0]))
        recs = obs.read_jsonl(str(tmp_path / "m.jsonl"))
        assert len(recs) == 1
        assert recs[0]["kind"] == "train_step"
        assert recs[0]["loss"] == 2.5
        assert recs[0]["tier_hist"] == [1.0, 2.0]
        assert "ts" in recs[0]

    def test_write_snapshot(self, tmp_path):
        sink = obs.JsonlSink(str(tmp_path / "m.jsonl"))
        with obs.scoped() as reg:
            reg.counter("x").inc(3)
            sink.write_snapshot(reg)
        recs = obs.read_jsonl(str(tmp_path / "m.jsonl"))
        assert recs[0]["kind"] == "snapshot"
        assert recs[0]["counters"]["x"] == 3.0


def _profile(tmp_path, body):
    """Run ``body`` under the JAX profiler on the CPU; returns
    {event name: [(start_ns, end_ns, stats)]} of every host event."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return events


class TestTrace:
    def test_trace_and_annotate_are_noop_safe(self):
        """``obs.span`` is the one span API (``obs.trace`` and
        ``obs.annotate`` are gone): with no profiler running and tracing
        off, the body runs, the stamps are ordered and nothing is
        recorded."""
        assert not hasattr(obs, "trace") and not hasattr(obs, "annotate")
        with obs.scoped() as reg:
            with obs.span("unit.test", cat="c", k=1) as sp:
                x = 1 + 1
        assert x == 2
        assert sp.t1 >= sp.t0 and sp.seconds == sp.t1 - sp.t0
        assert reg.spans() == []
        assert reg.snapshot()["histograms"] == {}

    def test_span_lands_in_profiler_trace(self, tmp_path):
        """Name, args as event stats and nesting, read back through
        ProfileData on the CPU."""
        def body():
            with obs.span("unit.outer", step=3):
                with obs.span("unit.inner", uid=7, slot=2) as sp:
                    sp.set(loss=1.5)

        events = _profile(tmp_path, body)
        [(o0, o1, o_stats)] = events["unit.outer"]
        [(i0, i1, i_stats)] = events["unit.inner"]
        assert o_stats == {"step": 3}
        assert i_stats == {"uid": 7, "slot": 2, "loss": 1.5}
        assert o0 <= i0 <= i1 <= o1

    def test_span_writes_annotation_with_tracing_off(self, tmp_path):
        assert not obs.tracing_enabled()
        with obs.scoped() as reg:
            def body():
                with obs.span("unit.untraced", cat="c"):
                    pass

            events = _profile(tmp_path, body)
        assert len(events["unit.untraced"]) == 1
        assert reg.spans() == []

    def test_span_hist_observes_body_seconds(self):
        import time

        with obs.scoped() as reg:
            with obs.span("unit.timed", hist="unit.seconds") as sp:
                time.sleep(0.01)
            h = reg.histogram("unit.seconds")
        assert h.count == 1
        assert h.total == sp.seconds >= 0.01
        assert reg.spans() == []


class TestHistogramWindow:
    def test_between_and_last_at_the_edges(self):
        h = obs.Histogram(max_samples=4)
        for v in range(3):
            h.observe(float(v))
        assert h.between(0, 3) == [0.0, 1.0, 2.0]
        assert h.between(1, 2) == [1.0]
        assert h.between(0, 0) == [] and h.between(3, 3) == []
        for bad in ((2, 1), (0, 4), (-1, 0)):
            with pytest.raises(ValueError):
                h.between(*bad)
        assert h.last(2) == [1.0, 2.0] and h.last(0) == []

    def test_between_is_none_after_the_reservoir_halves(self):
        h = obs.Histogram(max_samples=4)
        for v in range(5):               # the 5th halves [0..3] to [2, 3]
            h.observe(float(v))
        assert h.count == 5
        assert h.between(0, 5) is None and h.between(1, 3) is None
        assert h.between(2, 5) == [2.0, 3.0, 4.0]
        assert h.between(4, 5) == [4.0] and h.between(5, 5) == []
        assert h.last(2) == [3.0, 4.0]
        assert h.last(10) == [2.0, 3.0, 4.0]     # all it holds


class TestTrainerIntegration:
    def test_trainer_surfaces_mca_stats(self, tmp_path):
        """A short MCA-enabled training run must land per-step flops
        reduction + tier occupancy in the obs registry and the JSONL sink."""
        import jax
        from repro.configs import get_config
        from repro.core.policy import MCAConfig
        from repro.data import SyntheticLM
        from repro.models import build_model, reduced
        from repro.optim import adamw
        from repro.train.step import make_train_step
        from repro.train.trainer import Trainer, TrainerConfig

        cfg = reduced(get_config("starcoder2-3b"), n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_head=16, d_ff=128,
                      vocab_size=128,
                      mca=MCAConfig(enabled=True, alpha=0.4, block=16,
                                    sites=("v_proj",)))
        model = build_model(cfg)
        data = SyntheticLM(cfg.vocab_size, 16, 2, seed=0)
        step = jax.jit(make_train_step(model, adamw.AdamWConfig(lr=1e-3)))
        metrics_path = str(tmp_path / "metrics.jsonl")
        tcfg = TrainerConfig(total_steps=3, log_every=100,
                             metrics_path=metrics_path)
        with obs.scoped() as reg:
            res = Trainer(model, adamw.AdamWConfig(lr=1e-3), data, step,
                          tcfg).run()
            snap = reg.snapshot()
        assert res["steps"] == 3
        assert snap["counters"]["train.steps"] == 3
        assert snap["histograms"]["train.step_seconds"]["count"] == 3
        assert snap["gauges"]["train.flops_reduction"] > 1.0
        occ = [v for k, v in snap["counters"].items()
               if k.startswith("train.tier_occupancy.t")]
        assert occ and sum(occ) > 0
        # per-step record + final snapshot in the sink
        recs = obs.read_jsonl(metrics_path)
        steps = [r for r in recs if r["kind"] == "train_step"]
        assert len(steps) == 3
        assert steps[-1]["flops_reduction"] > 1.0
        assert len(steps[-1]["tier_hist"]) == cfg.mca.n_tiers
        assert recs[-1]["kind"] == "snapshot"
        # trainer history mirrors the records
        assert res["history"][-1]["flops_reduction"] > 1.0

    def test_trainer_observes_phase_histograms(self):
        """Once per step: the dispatch's seconds, and the step's host
        time (its wall time less the dispatch and the loss wait)."""
        import jax
        from repro.configs import get_config
        from repro.data import SyntheticLM
        from repro.models import build_model, reduced
        from repro.optim import adamw
        from repro.train.step import make_train_step
        from repro.train.trainer import Trainer, TrainerConfig

        cfg = reduced(get_config("starcoder2-3b"), n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=1, d_head=16, d_ff=64,
                      vocab_size=64)
        model = build_model(cfg)
        data = SyntheticLM(cfg.vocab_size, 8, 2, seed=0)
        opt = adamw.AdamWConfig(lr=1e-3)
        step = jax.jit(make_train_step(model, opt))
        with obs.scoped() as reg:
            Trainer(model, opt, data, step,
                    TrainerConfig(total_steps=3, log_every=100)).run()
            snap = reg.snapshot()
        h = snap["histograms"]
        assert h["train.dispatch_seconds"]["count"] == 3
        assert h["train.host_seconds"]["count"] == 3
        assert h["train.host_seconds"]["min"] >= 0.0
        assert h["train.dispatch_seconds"]["sum"] <= \
            h["train.step_seconds"]["sum"]


class TestTracing:
    def test_disabled_is_complete_noop(self):
        """With tracing off, span machinery records no registry span and
        does not raise; a span's ``hist`` is the one registry write that
        does not wait for tracing."""
        assert not obs.tracing_enabled()
        with obs.scoped() as reg:
            with obs.span("x", cat="c", extra=1):
                pass
            with obs.span("w", cat="c", hist="h.seconds"):
                pass
            obs.record_span("y", 0.0, 1.0, cat="c")
            obs.mark("z", cat="c")
        assert reg.spans() == []
        snap = reg.snapshot()
        assert list(snap["histograms"]) == ["h.seconds"]
        assert snap["histograms"]["h.seconds"]["count"] == 1
        assert snap["counters"] == {} and snap["gauges"] == {}

    def test_noop_inside_jit(self):
        """Span calls inside jit-traced Python: no exceptions, no registry
        writes while disabled (trace-time Python runs once per compile)."""
        import jax

        with obs.scoped() as reg:
            @jax.jit
            def f(x):
                with obs.span("traced", cat="jit"):
                    obs.mark("inside", cat="jit")
                    return x * 2

            assert int(f(jnp.asarray(3))) == 6
            assert int(f(jnp.asarray(4))) == 8     # cached executable too
        assert reg.spans() == []

    def test_tracing_ctx_restores_prior_state(self):
        assert not obs.tracing_enabled()
        with obs.tracing():
            assert obs.tracing_enabled()
            with obs.tracing(False):
                assert not obs.tracing_enabled()
            assert obs.tracing_enabled()
        assert not obs.tracing_enabled()

    def test_span_records_interval_args_and_error(self):
        with obs.scoped() as reg, obs.tracing():
            with obs.span("ok", cat="t", track="tr", k=1):
                pass
            with pytest.raises(ValueError):
                with obs.span("boom", cat="t"):
                    raise ValueError("x")
            obs.record_span("manual", 10.0, 10.5, cat="t", args={"a": 2})
            obs.mark("instant", cat="t", track="tr")
        spans = reg.spans()
        by_name = {s["name"]: s for s in spans}
        assert set(by_name) == {"ok", "boom", "manual", "instant"}
        assert by_name["ok"]["track"] == "tr"
        assert by_name["ok"]["args"] == {"k": 1}
        assert by_name["ok"]["dur"] >= 0.0
        assert by_name["boom"]["args"]["error"] == "ValueError"
        assert by_name["boom"]["track"] == "t"     # falls back to cat
        assert by_name["manual"]["dur"] == 0.5
        assert by_name["instant"]["dur"] == 0.0

    def test_span_deque_bounded_and_drop_counted(self, monkeypatch):
        monkeypatch.setattr(obs.Registry, "MAX_SPANS", 4)
        reg = obs.Registry()
        with obs.scoped(reg), obs.tracing():
            for i in range(7):
                obs.mark(f"s{i}", cat="t")
        assert len(reg.spans()) == 4
        assert reg.spans_dropped == 3
        assert reg.spans()[0]["name"] == "s3"      # oldest evicted first

    def test_export_chrome_trace(self, tmp_path):
        with obs.scoped() as reg, obs.tracing():
            obs.record_span("a", 5.0, 5.25, cat="c1", track="t1",
                            args={"k": 1})
            obs.record_span("b", 5.1, 5.2, cat="c2", track="t2")
        path = str(tmp_path / "trace.json")
        trace = obs.export_chrome_trace(path, registry=reg)
        on_disk = json.loads(open(path).read())
        assert on_disk == json.loads(json.dumps(trace))
        evs = trace["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        xs = [e for e in evs if e["ph"] == "X"]
        assert {m["args"]["name"] for m in meta} == {"repro", "t1", "t2"}
        assert len(xs) == 2
        a = next(e for e in xs if e["name"] == "a")
        b = next(e for e in xs if e["name"] == "b")
        assert a["ts"] == 0.0 and a["dur"] == 250_000.0     # rebased, us
        assert b["ts"] == 100_000.0 and b["dur"] == 100_000.0
        assert a["tid"] != b["tid"]                # one timeline per track
        assert a["args"] == {"k": 1}

    def test_export_empty_registry(self, tmp_path):
        trace = obs.export_chrome_trace(str(tmp_path / "e.json"),
                                        registry=obs.Registry())
        assert [e["ph"] for e in trace["traceEvents"]] == ["M"]


class TestRequestChains:
    """Acceptance: one complete queue->prefill->decode->finish chain per
    request, from each batcher, exported as valid Chrome-trace JSON."""

    @pytest.fixture(scope="class")
    def serve_setup(self):
        import jax
        from repro.configs import get_config
        from repro.models import build_model, reduced

        cfg = reduced(get_config("starcoder2-3b"), n_layers=2,
                      vocab_size=128)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        return cfg, model, params

    def _serve_traced(self, serve_setup, batcher_cls, **kw):
        from repro.serve import Engine, Request

        cfg, model, params = serve_setup
        eng = Engine(model, params, batch_size=2, max_len=64)
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
                   for n in (4, 9, 6)]
        with obs.scoped() as reg, obs.tracing():
            b = batcher_cls(eng, **kw)
            for i, p in enumerate(prompts):
                assert b.submit(Request(uid=i, prompt=p,
                                        max_new=4)) == "queued"
            b.run()
        assert all(b.status[i] == "ok" for i in range(len(prompts)))
        return reg, len(prompts)

    def _check_chains(self, reg, n_req, cat):
        chains = {}
        for s in reg.spans():
            if s["track"].startswith(f"{cat}/req"):
                chains.setdefault(s["track"], []).append(s)
        assert len(chains) == n_req, sorted(chains)
        for track, spans in chains.items():
            names = [s["name"] for s in spans]
            assert names[0] == "queue", (track, names)
            assert names[1] == "prefill", (track, names)
            assert names[-1] == "finish", (track, names)
            decodes = names[2:-1]
            assert decodes and set(decodes) == {"decode"}, (track, names)
            # same perf_counter clock: phases are ordered in time
            end = [s["ts"] + s["dur"] for s in spans]
            start = [s["ts"] for s in spans]
            assert all(start[i + 1] >= end[i] - 1e-3
                       for i in range(len(spans) - 1)), (track, names)
            assert spans[-1]["args"]["status"] == "ok"

    @pytest.mark.parametrize("which", ["wave", "per_slot"])
    def test_batcher_emits_complete_chains(self, serve_setup, which,
                                           tmp_path):
        from repro.serve import ContinuousBatcher, SlotBatcher

        cls, cat, kw = {
            "wave": (ContinuousBatcher, "serve.wave", {}),
            "per_slot": (SlotBatcher, "serve.per_slot",
                         {"check_every": 4}),
        }[which]
        reg, n_req = self._serve_traced(serve_setup, cls, **kw)
        self._check_chains(reg, n_req, cat)
        # and the export round-trips as valid Chrome-trace JSON
        path = str(tmp_path / f"{which}.json")
        obs.export_chrome_trace(path, registry=reg)
        trace = json.loads(open(path).read())
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == len(reg.spans())
        assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)

    def test_slot_batcher_observes_request_histograms(self, serve_setup):
        """Tracing off: ``serve.queue_seconds`` once per insertion,
        ``serve.inter_token_seconds`` once per finished request of two or
        more tokens, the batcher's host time once per phase run."""
        from repro.serve import Engine, Request, SlotBatcher

        cfg, model, params = serve_setup
        eng = Engine(model, params, batch_size=2, max_len=64)
        rng = np.random.default_rng(13)
        reqs = [Request(uid=i, max_new=m, prompt=rng.integers(
            1, cfg.vocab_size, size=n).astype(np.int32))
            for i, (n, m) in enumerate(((4, 1), (9, 4), (6, 3)))]
        with obs.scoped() as reg:
            b = SlotBatcher(eng, check_every=2)
            for r in reqs:
                assert b.submit(r) == "queued"
            b.run()
            snap = reg.snapshot()
        h = snap["histograms"]
        assert h["serve.queue_seconds"]["count"] == 3
        assert h["serve.inter_token_seconds"]["count"] == 2  # not max_new 1
        assert h["serve.batcher_host_seconds"]["count"] > 0
        assert h["serve.batcher_host_seconds"]["min"] >= 0.0
        for r in reqs:
            assert r.status == "ok"
            assert r.submit_pc <= r.insert_pc <= r.first_pc <= r.finish_pc
        assert reg.spans() == []

    def test_no_spans_when_tracing_disabled(self, serve_setup):
        """Serving with tracing off must leave the registry span-free."""
        from repro.serve import SlotBatcher

        reg, _ = self._serve_traced_disabled(serve_setup, SlotBatcher,
                                             check_every=4)
        assert reg.spans() == []

    def _serve_traced_disabled(self, serve_setup, batcher_cls, **kw):
        from repro.serve import Engine, Request

        cfg, model, params = serve_setup
        eng = Engine(model, params, batch_size=2, max_len=64)
        rng = np.random.default_rng(12)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
                   for n in (4, 6)]
        with obs.scoped() as reg:
            b = batcher_cls(eng, **kw)
            for i, p in enumerate(prompts):
                b.submit(Request(uid=i, prompt=p, max_new=3))
            b.run()
        return reg, len(prompts)


class TestSinkCrashSafety:
    def test_write_flushes_immediately(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        sink = obs.JsonlSink(path)
        sink.write("a", i=1)
        # visible to a second reader BEFORE close (per-write flush)
        assert obs.read_jsonl(path)[0]["i"] == 1
        sink.close()

    def test_closed_sink_raises(self, tmp_path):
        sink = obs.JsonlSink(str(tmp_path / "m.jsonl"))
        sink.close()
        with pytest.raises(ValueError, match="closed"):
            sink.write("late")
        sink.close()                               # idempotent

    def test_context_manager(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with obs.JsonlSink(path) as sink:
            sink.write("a", i=1)
        assert obs.read_jsonl(path)[0]["i"] == 1

    def test_threaded_writes_interleave_whole_lines(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        sink = obs.JsonlSink(path)

        def worker(tid):
            for i in range(50):
                sink.write("w", tid=tid, i=i, pad="x" * 64)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sink.close()
        recs = obs.read_jsonl(path)
        assert len(recs) == 200
        seen = {(r["tid"], r["i"]) for r in recs}
        assert len(seen) == 200                    # nothing torn or lost

    def test_killed_writer_leaves_only_complete_lines(self, tmp_path):
        """Regression: SIGKILL mid-stream must not leave partial JSON
        (each record is one flushed write; nothing buffers across
        records)."""
        import os
        import subprocess
        import sys
        import time

        path = str(tmp_path / "kill.jsonl")
        script = (
            "from repro.obs import JsonlSink\n"
            f"s = JsonlSink({path!r})\n"
            "i = 0\n"
            "while True:\n"
            "    s.write('spin', i=i, pad='x' * 200)\n"
            "    i += 1\n"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        proc = subprocess.Popen([sys.executable, "-c", script], env=env)
        try:
            deadline = time.time() + 60
            while time.time() < deadline:
                if os.path.exists(path) and os.path.getsize(path) > 8192:
                    break
                time.sleep(0.05)
            else:
                raise AssertionError("writer produced no output")
        finally:
            proc.kill()
            proc.wait()
        lines = open(path).read().splitlines()
        assert len(lines) >= 10
        for ln in lines:
            rec = json.loads(ln)                   # every line is whole
            assert rec["kind"] == "spin"


class TestScopedThreads:
    def test_nested_scopes_do_not_leak_across_threads(self):
        """Satellite: concurrent threads each nest scoped() registries;
        counts must stay per-thread and the global must be untouched."""
        g = obs.get_registry()
        before = g.counter("thread.test").value
        errors = []
        start = threading.Barrier(6)

        def worker(i):
            try:
                start.wait(timeout=30)
                for _ in range(20):
                    with obs.scoped() as outer:
                        assert obs.get_registry() is outer
                        outer.counter("thread.test").inc(i)
                        with obs.scoped() as inner:
                            assert obs.get_registry() is inner
                            inner.counter("thread.test").inc(1000)
                        assert obs.get_registry() is outer
                        assert outer.counter("thread.test").value == i
                        assert inner.counter("thread.test").value == 1000
            except Exception as e:                 # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i + 1,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert g.counter("thread.test").value == before
        assert obs.get_registry() is g


class TestAggregate:
    def test_world1_psum_equals_local(self):
        """Single process, single device: aggregate='psum' must be the
        plain local snapshot."""
        with obs.scoped() as reg:
            reg.counter("a").inc(3)
            h = reg.histogram("h")
            h.observe(2.0)
            h.observe(4.0)
            local = reg.snapshot()
            agg = obs.snapshot(aggregate="psum")
        assert agg["counters"]["a"] == local["counters"]["a"] == 3.0
        assert agg["histograms"]["h"]["count"] == 2
        assert agg["histograms"]["h"]["sum"] == 6.0
        assert agg["histograms"]["h"]["min"] == 2.0
        assert agg["histograms"]["h"]["max"] == 4.0

    def test_default_is_local(self):
        with obs.scoped() as reg:
            reg.counter("b").inc(2)
            snap = obs.snapshot()
        assert snap == reg.snapshot()

    def test_unknown_aggregate_rejected(self):
        with pytest.raises(ValueError, match="aggregate"):
            obs.snapshot(aggregate="allgather")

    def test_summary_has_p99(self):
        h = obs.Histogram()
        for v in range(200):
            h.observe(float(v))
        s = h.summary()
        assert s["p99"] >= s["p95"] >= s["p50"]
        assert s["p99"] >= 190.0
