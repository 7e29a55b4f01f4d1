"""Fault-injection + graceful-degradation suite (repro.resilience).

Drives every canonical injection point end-to-end — NaN logits, slow
steps, checkpoint write failures, corrupt checkpoints, data stalls,
oversized prompts, queue overflow — and asserts the system *recovers
without a process crash*, that the exact-attention fallback wave is
token-identical to an MCA-off engine, and that every recovery event is
visible as a ``resilience.*`` counter in an ``obs.scoped()`` snapshot.
"""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, resilience
from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_config
from repro.core import amm
from repro.data import Prefetcher, SyntheticLM
from repro.models import build_model, reduced
from repro.optim import adamw
from repro.resilience import Fault, FaultInjected, NonFiniteError
from repro.serve import ContinuousBatcher, Engine, Request
from repro.train import Trainer, TrainerConfig, TrainingDivergedError

jax.config.update("jax_platform_name", "cpu")


# ====================================================== injection core ==
class TestInjection:
    def test_noop_without_chaos(self):
        assert resilience.inject("serve.prefill", 42) == 42
        assert not resilience.active()

    def test_canonical_points_registered(self):
        assert set(resilience.CANONICAL_POINTS) <= set(resilience.points())

    def test_raise_mode_and_counter(self):
        with obs.scoped() as reg:
            with resilience.chaos(Fault("ckpt.write", mode="raise")):
                with pytest.raises(FaultInjected):
                    resilience.inject("ckpt.write")
            snap = reg.snapshot()
        assert snap["counters"]["resilience.injected.ckpt.write"] == 1

    def test_delay_mode(self):
        with resilience.chaos(Fault("data.batch", mode="delay",
                                    delay_s=0.05)):
            t0 = time.perf_counter()
            out = resilience.inject("data.batch", "v")
            assert out == "v"
            assert time.perf_counter() - t0 >= 0.05

    def test_corrupt_mode_nan_poisons(self):
        with resilience.chaos(Fault("serve.prefill", mode="corrupt")):
            out = resilience.inject("serve.prefill",
                                    np.ones((4, 4), np.float32))
        assert np.isnan(out).any()
        assert resilience.inject("serve.prefill", 1.0) == 1.0  # plan popped

    def test_after_and_times_windows(self):
        with resilience.chaos(Fault("train.loss", mode="corrupt",
                                    after=1, times=2)):
            hits = [resilience.inject("train.loss", 1.0) for _ in range(5)]
        finite = [np.isfinite(h) for h in hits]
        assert finite == [True, False, False, True, True]

    def test_deterministic_seeded_probability(self):
        def run():
            with resilience.chaos(Fault("train.loss", mode="corrupt",
                                        times=None, p=0.5, seed=3)):
                return [np.isfinite(resilience.inject("train.loss", 1.0))
                        for _ in range(20)]
        a, b = run(), run()
        assert a == b                    # seeded => identical firing pattern
        assert any(a) and not all(a)     # coin actually mixes

    def test_chaos_restores_on_exception(self):
        with pytest.raises(RuntimeError):
            with resilience.chaos(Fault("train.step", mode="raise")):
                raise RuntimeError("boom")
        assert not resilience.active()


# ======================================================= numeric guards ==
class TestGuards:
    def test_is_finite(self):
        assert resilience.is_finite(1.0)
        assert not resilience.is_finite(float("nan"))
        assert not resilience.is_finite(np.asarray([1.0, np.inf]))
        assert resilience.is_finite(np.asarray([1, 2], np.int32))

    def test_check_finite_raises(self):
        with pytest.raises(NonFiniteError, match="wave logits"):
            resilience.check_finite(np.asarray([np.nan]), "wave logits")

    def test_amm_probs_survive_nan_norms(self):
        """Corrupted block norms must still yield a valid distribution."""
        w = jnp.ones((64, 8))
        with resilience.chaos(Fault("amm.probs", mode="corrupt")):
            p = amm.block_probs(w, block=16)
        p = np.asarray(p)
        assert np.isfinite(p).all() and p.min() >= 0
        np.testing.assert_allclose(p.sum(), 1.0, rtol=1e-6)

    def test_amm_probs_all_zero_weights_uniform(self):
        p = np.asarray(amm.block_probs(jnp.zeros((64, 8)), block=16))
        np.testing.assert_allclose(p, 0.25, rtol=1e-5)

    def test_amm_estimator_weights_finite_on_degenerate_p(self):
        probs = jnp.asarray([0.0, float("nan"), 1.0, 0.0])
        idx, inv_rp = amm.draw_block_samples(jax.random.PRNGKey(0),
                                             probs, r=8)
        assert np.isfinite(np.asarray(inv_rp)).all()


# ==================================================== checkpoint layer ==
def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}


def _corrupt_npz(step_dir):
    """Flip payload bytes mid-file (zip headers live at start/end)."""
    path = os.path.join(step_dir, "arrays.npz")
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        f.write(b"\xff" * 8)


class TestCheckpointIntegrity:
    def test_corrupt_array_detected(self, tmp_path):
        d = ckpt.save(str(tmp_path), 1, _tree())
        _corrupt_npz(d)
        with pytest.raises(ckpt.CheckpointCorruptError):
            ckpt.restore(str(tmp_path), 1, jax.eval_shape(_tree))

    def test_restore_latest_valid_falls_back(self, tmp_path):
        tree = _tree()
        ckpt.save(str(tmp_path), 1, tree)
        d2 = ckpt.save(str(tmp_path), 2, tree)
        _corrupt_npz(d2)
        with obs.scoped() as reg:
            step, out = ckpt.restore_latest_valid(str(tmp_path),
                                                  jax.eval_shape(_tree))
            snap = reg.snapshot()
        assert step == 1
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(tree["a"]))
        assert snap["counters"]["resilience.ckpt.corrupt_skipped"] == 1

    def test_latest_step_skips_torn_dirs(self, tmp_path):
        ckpt.save(str(tmp_path), 3, _tree())
        os.makedirs(tmp_path / "step_00000099")          # no manifest
        assert ckpt.latest_step(str(tmp_path)) == 3

    def test_stale_tmp_cleanup(self, tmp_path):
        os.makedirs(tmp_path / "step_00000007.tmp")
        with obs.scoped() as reg:
            assert ckpt.cleanup_stale_tmp(str(tmp_path)) == 1
            snap = reg.snapshot()
        assert not (tmp_path / "step_00000007.tmp").exists()
        assert snap["counters"]["resilience.ckpt.stale_tmp_removed"] == 1

    def test_async_checkpointer_cleans_tmp_on_startup(self, tmp_path):
        os.makedirs(tmp_path / "step_00000001.tmp")
        ckpt.AsyncCheckpointer(str(tmp_path))
        assert not (tmp_path / "step_00000001.tmp").exists()

    def test_structure_mismatch_names_path(self, tmp_path):
        ckpt.save(str(tmp_path), 1, _tree())
        with pytest.raises(ckpt.StructureMismatchError, match=r"\['a'\]"):
            ckpt.restore(str(tmp_path), 1, {"x": jnp.zeros((2,))})

    def test_restore_latest_valid_skips_structure_mismatch(self, tmp_path):
        """Regression: a stale checkpoint from an older model config in
        the same dir must be walked past, not crash the restore."""
        tree = _tree()
        ckpt.save(str(tmp_path), 1, tree)
        ckpt.save(str(tmp_path), 2, {"x": jnp.zeros((2,))})  # old config
        with obs.scoped() as reg:
            step, out = ckpt.restore_latest_valid(str(tmp_path),
                                                  jax.eval_shape(_tree))
            snap = reg.snapshot()
        assert step == 1
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(tree["a"]))
        assert snap["counters"]["resilience.ckpt.structure_skipped"] == 1

    def test_async_write_failure_reraised_from_wait(self, tmp_path):
        """Regression: a failed write used to die silently on the thread."""
        with obs.scoped() as reg:
            c = ckpt.AsyncCheckpointer(str(tmp_path))
            with resilience.chaos(Fault("ckpt.write", mode="raise")):
                c.save(1, _tree())
                with pytest.raises(FaultInjected):
                    c.wait()
            snap = reg.snapshot()
        assert snap["counters"]["resilience.ckpt.write_failures"] == 1
        assert ckpt.latest_step(str(tmp_path)) is None
        c.save(2, _tree())                    # checkpointer still usable
        c.wait()
        assert ckpt.latest_step(str(tmp_path)) == 2

    def test_async_write_failure_surfaces_before_next_save(self, tmp_path):
        c = ckpt.AsyncCheckpointer(str(tmp_path))
        with resilience.chaos(Fault("ckpt.write", mode="raise")):
            c.save(1, _tree())
            time.sleep(0.05)                  # let the write thread fail
            with pytest.raises(FaultInjected):
                c.save(2, _tree())


# ==================================================== trainer hardening ==
class _ToyModel:
    """Deterministic 1-param 'model': good steps add mean(tokens)-coupled
    increments so the loss trajectory is a pure function of the data
    stream (what kill-and-resume must replay exactly)."""

    def init(self, key):
        return {"w": jnp.zeros(())}


def _toy_step(params, opt_state, batch):
    tok_mean = jnp.mean(batch["tokens"].astype(jnp.float32))
    w = params["w"] + 1.0
    loss = jnp.abs(tok_mean - w) / (tok_mean + 1.0)
    opt_state = dict(opt_state)
    opt_state["count"] = opt_state["count"] + 1
    return {"w": w}, opt_state, {"total_loss": loss}


def _toy_trainer(tmp_path, total_steps=6, **cfg_kw):
    data = SyntheticLM(32, 8, 2, seed=0)
    tcfg = TrainerConfig(total_steps=total_steps,
                         ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=1,
                         log_every=100, watchdog_s=600, **cfg_kw)
    return Trainer(_ToyModel(), adamw.AdamWConfig(), data, _toy_step, tcfg)


class TestTrainerHardening:
    def test_nan_loss_skips_step(self, tmp_path):
        with obs.scoped() as reg:
            tr = _toy_trainer(tmp_path, total_steps=5, max_bad_steps=10)
            with resilience.chaos(Fault("train.loss", mode="corrupt",
                                        after=1, times=2)):
                out = tr.run()
            snap = reg.snapshot()
        assert snap["counters"]["train.skipped_steps"] == 2
        statuses = [h["status"] for h in out["history"]]
        assert statuses.count("skipped") == 2
        # 5 steps, 2 skipped -> only 3 applied updates
        assert float(tr.params["w"]) == 3.0

    def test_rollback_after_consecutive_bad_steps(self, tmp_path):
        with obs.scoped() as reg:
            tr = _toy_trainer(tmp_path, total_steps=5, max_bad_steps=2)
            with resilience.chaos(Fault("train.loss", mode="corrupt",
                                        after=2, times=2)):
                tr.run()
            snap = reg.snapshot()
        assert snap["counters"]["resilience.train.rollbacks"] == 1
        assert snap["counters"]["train.skipped_steps"] == 2
        # rollback restored step-2 state, then steps 3..5 applied cleanly
        assert float(tr.params["w"]) == 5.0

    def test_rollback_bounded_aborts_on_persistent_divergence(self,
                                                              tmp_path):
        """Regression: deterministic replay means a rollback re-runs the
        same bad batches — unbounded rollbacks livelock forever; past
        max_rollbacks the trainer must abort instead."""
        with obs.scoped() as reg:
            tr = _toy_trainer(tmp_path, total_steps=4, max_bad_steps=2,
                              max_rollbacks=1)
            # a valid step-0 checkpoint to roll back to, written
            # synchronously so the test never races the async checkpointer
            ckpt.save(str(tmp_path / "ckpt"), 0,
                      {"params": tr.params, "opt": tr.opt_state})
            with resilience.chaos(Fault("train.loss", mode="corrupt",
                                        times=None)):
                with pytest.raises(TrainingDivergedError,
                                   match="deterministic replay"):
                    tr.run()
            snap = reg.snapshot()
        assert tr.rollbacks == 1
        assert snap["counters"]["resilience.train.rollbacks"] == 1

    def test_donating_step_rejected_with_finite_checks(self, tmp_path):
        """Regression: finite_checks reuses pre-step buffers, which a
        donating train_step frees on device — the inconsistent wiring
        must fail loudly at init, not with 'Array has been deleted' on
        the first skipped step (which CPU CI would never see)."""
        data = SyntheticLM(32, 8, 2, seed=0)
        with pytest.raises(ValueError, match="non-donating"):
            Trainer(_ToyModel(), adamw.AdamWConfig(), data, _toy_step,
                    TrainerConfig(total_steps=1), step_donates=True)
        # with the guard off, donation is a legitimate perf choice
        Trainer(_ToyModel(), adamw.AdamWConfig(), data, _toy_step,
                TrainerConfig(total_steps=1, finite_checks=False),
                step_donates=True)

    def test_watchdog_escalates_to_recovery_cb(self, tmp_path):
        calls = []
        with obs.scoped() as reg:
            tr = _toy_trainer(tmp_path, total_steps=1,
                              watchdog_escalate_after=1,
                              recovery_cb=calls.append)
            tr.cfg.watchdog_s = 0.05
            tr.watchdog.deadline = 0.05
            with resilience.chaos(Fault("train.step", mode="delay",
                                        delay_s=0.3)):
                out = tr.run()
            snap = reg.snapshot()
        assert out["watchdog_fired"] >= 1
        assert calls, "recovery callback never invoked"
        assert snap["counters"]["resilience.train.watchdog_fired"] >= 1
        assert snap["counters"][
            "resilience.train.watchdog_escalations"] >= 1

    def test_ckpt_write_failure_does_not_kill_training(self, tmp_path):
        with obs.scoped() as reg:
            tr = _toy_trainer(tmp_path, total_steps=4)
            with resilience.chaos(Fault("ckpt.write", mode="raise",
                                        times=2)):
                out = tr.run()
            snap = reg.snapshot()
        assert out["steps"] == 4                      # no crash
        assert out["ckpt_errors"] >= 1
        assert snap["counters"]["resilience.train.ckpt_failures"] >= 1
        assert snap["counters"]["resilience.ckpt.write_failures"] == 2
        # later writes landed despite the early failures
        assert ckpt.latest_step(str(tmp_path / "ckpt")) == 4

    def test_data_stall_injection_is_survivable(self, tmp_path):
        with obs.scoped() as reg:
            tr = _toy_trainer(tmp_path, total_steps=3)
            with resilience.chaos(Fault("data.batch", mode="delay",
                                        delay_s=0.05, times=1)):
                out = tr.run()
            snap = reg.snapshot()
        assert out["steps"] == 3
        assert snap["counters"]["resilience.injected.data.batch"] == 1

    def test_kill_and_resume_matches_uninterrupted(self, tmp_path):
        """SIGKILL-style interruption (exception mid-run, no wait()):
        restart restores the latest valid checkpoint, replays
        data.batch(step) deterministically, and the loss trajectory +
        final params match an uninterrupted run."""
        # interrupted run: hard-raise inside step 5 of 8 (no cleanup path)
        tr1 = _toy_trainer(tmp_path, total_steps=8)
        with resilience.chaos(Fault("train.step", mode="raise", after=4)):
            with pytest.raises(FaultInjected):
                tr1.run()
        # async writes from completed steps may still be in flight; a real
        # SIGKILL would leave at most a torn .tmp, which restore skips.
        # save(N) joins the write of N-1 first, so >= step 3 is durable.
        tr2 = _toy_trainer(tmp_path, total_steps=8)
        assert tr2.start_step in (3, 4)   # latest *valid* checkpoint
        out2 = tr2.run()

        ref = _toy_trainer(tmp_path / "ref", total_steps=8)
        out_ref = ref.run()
        np.testing.assert_allclose(float(tr2.params["w"]),
                                   float(ref.params["w"]))
        resumed = {h["step"]: h["loss"] for h in out2["history"]}
        for h in out_ref["history"]:
            if h["step"] in resumed:
                np.testing.assert_allclose(resumed[h["step"]], h["loss"],
                                           rtol=1e-6)

    def test_trainer_init_skips_corrupt_latest(self, tmp_path):
        tr1 = _toy_trainer(tmp_path, total_steps=3)
        tr1.run()
        _corrupt_npz(str(tmp_path / "ckpt" / "step_00000003"))
        tr2 = _toy_trainer(tmp_path, total_steps=3)
        assert tr2.start_step == 2        # fell back past the corrupt step

    def test_prefetcher_propagates_source_crash(self):
        class Bad:
            def batch(self, step):
                raise OSError("disk gone")
        pf = Prefetcher(Bad(), depth=1)
        with pytest.raises(OSError, match="disk gone"):
            pf.next()
        # regression: the worker thread is gone — every later call must
        # fail fast too, not block forever on the empty queue
        with pytest.raises(OSError, match="disk gone"):
            pf.next()
        pf.close()


# ===================================================== serve hardening ==
@pytest.fixture(scope="module")
def serve_setup():
    cfg = reduced(get_config("starcoder2-3b"), n_layers=1, vocab_size=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, batch_size=2, max_len=32)
    return cfg, model, params, eng


@pytest.fixture(scope="module")
def mca_setup():
    from repro.core.policy import MCAConfig
    cfg = reduced(get_config("starcoder2-3b"), n_layers=1, vocab_size=128,
                  mca=MCAConfig(enabled=True, alpha=0.4, block=16,
                                sites=("v_proj",)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng_on = Engine(model, params, batch_size=2, max_len=32,
                    mca_enabled=True)
    eng_off = Engine(model, params, batch_size=2, max_len=32,
                     mca_enabled=False)
    return cfg, eng_on, eng_off


class TestServeAdmission:
    def test_oversized_prompt_rejected(self, serve_setup):
        cfg, model, params, eng = serve_setup
        b = ContinuousBatcher(eng)
        long_prompt = np.ones(40, np.int32)          # 40 + 4 > max_len 32
        with obs.scoped() as reg:
            status = b.submit(Request(uid=0, prompt=long_prompt, max_new=4))
            snap = reg.snapshot()
        assert status == "rejected"
        assert b.status[0] == "rejected"
        assert snap["counters"]["serve.rejected.prompt_too_long"] == 1
        assert not b.queue                           # never enters a wave

    def test_queue_overflow_rejected(self, serve_setup):
        cfg, model, params, eng = serve_setup
        b = ContinuousBatcher(eng, max_queue=2)
        p = np.ones(4, np.int32)
        with obs.scoped() as reg:
            sts = [b.submit(Request(uid=i, prompt=p, max_new=2))
                   for i in range(3)]
            snap = reg.snapshot()
        assert sts == ["queued", "queued", "rejected"]
        assert snap["counters"]["serve.rejected.queue_full"] == 1

    def test_engine_generate_validates_cache_capacity(self, serve_setup):
        cfg, model, params, eng = serve_setup
        prompts = np.ones((2, 30), np.int32)
        with pytest.raises(ValueError, match="overruns"):
            eng.generate(prompts, max_new=8)

    def test_wave_assembly_is_capacity_aware(self, serve_setup):
        """Regression: two individually-admissible requests whose joint
        max(prompt)+max(max_new) overruns max_len used to be batched into
        one wave, fail generate's capacity check deterministically, and
        take the whole wave down as FAILED.  They must run in separate
        waves instead."""
        cfg, model, params, eng = serve_setup      # max_len=32, batch=2
        b = ContinuousBatcher(eng)
        b.submit(Request(uid=0, prompt=np.ones(20, np.int32), max_new=4))
        b.submit(Request(uid=1, prompt=np.ones(4, np.int32), max_new=20))
        with obs.scoped() as reg:
            done = b.run()
            snap = reg.snapshot()
        assert b.status == {0: "ok", 1: "ok"}
        assert len(done[0]) == 4 and len(done[1]) == 20
        assert snap["counters"]["serve.waves"] == 2
        # deterministic capacity errors must not burn retries
        assert "resilience.serve.wave_retries" not in snap["counters"]

    def test_deadline_timeout(self, serve_setup):
        cfg, model, params, eng = serve_setup
        b = ContinuousBatcher(eng)
        p = np.ones(4, np.int32)
        b.submit(Request(uid=0, prompt=p, max_new=2, deadline_s=0.0))
        b.submit(Request(uid=1, prompt=p, max_new=2))
        time.sleep(0.01)
        with obs.scoped() as reg:
            done = b.run()
            snap = reg.snapshot()
        assert b.status[0] == "timeout" and 0 not in done
        assert b.status[1] == "ok" and 1 in done
        assert snap["counters"]["resilience.serve.timeouts"] == 1

    def test_dummy_slots_excluded_from_metrics(self, serve_setup):
        """Satellite: a half-empty wave must not double-count tokens."""
        cfg, model, params, eng = serve_setup
        b = ContinuousBatcher(eng)
        rng = np.random.default_rng(0)
        with obs.scoped() as reg:
            b.submit(Request(uid=0, max_new=4,
                             prompt=rng.integers(1, 128, 6).astype(np.int32)))
            b.run()                      # 1 real request, 1 dummy slot
            snap = reg.snapshot()
        assert snap["counters"]["serve.generated_tokens"] == 4
        c = snap["counters"]
        assert 1 - c["serve.slot_idle_steps"] / c["serve.slot_steps"] == 0.5


class TestServeDegradation:
    def test_nan_logits_degrade_to_exact_and_match_mca_off(self, mca_setup):
        """Acceptance: the exact-attention fallback wave is token-identical
        to an MCA-off engine on the same prompts/params."""
        cfg, eng_on, eng_off = mca_setup
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
                   for _ in range(2)]
        want = eng_off.generate(np.stack(prompts), max_new=4)

        b = ContinuousBatcher(eng_on)
        for uid, p in enumerate(prompts):
            b.submit(Request(uid=uid, prompt=p, max_new=4))
        with obs.scoped() as reg:
            # poison the first (MCA) attempt's logits; the exact retry
            # passes the finite check untouched
            with resilience.chaos(Fault("serve.prefill", mode="corrupt",
                                        times=1)):
                done = b.run()
            snap = reg.snapshot()
        assert b.status == {0: "degraded", 1: "degraded"}
        for uid in (0, 1):
            assert done[uid] == want[uid].tolist()
        assert snap["counters"]["resilience.serve.wave_retries"] == 1
        assert snap["counters"]["resilience.serve.degraded_waves"] == 1
        assert snap["counters"][
            "resilience.injected.serve.prefill"] == 1

    def test_decode_fault_retries_wave(self, mca_setup):
        cfg, eng_on, eng_off = mca_setup
        p = np.ones(5, np.int32)
        b = ContinuousBatcher(eng_on)
        b.submit(Request(uid=0, prompt=p, max_new=3))
        with obs.scoped() as reg:
            with resilience.chaos(Fault("serve.decode", mode="raise",
                                        times=1)):
                done = b.run()
            snap = reg.snapshot()
        assert 0 in done and b.status[0] == "degraded"
        assert snap["counters"]["resilience.serve.wave_retries"] == 1

    def test_persistent_fault_fails_wave_without_crash(self, serve_setup):
        cfg, model, params, eng = serve_setup
        b = ContinuousBatcher(eng, max_retries=1, backoff_s=0.0)
        p = np.ones(4, np.int32)
        b.submit(Request(uid=0, prompt=p, max_new=2))
        b.submit(Request(uid=1, prompt=p, max_new=2))
        with obs.scoped() as reg:
            with resilience.chaos(Fault("serve.prefill", mode="corrupt",
                                        times=None)):
                done = b.run()                       # exhausts the ladder
            snap = reg.snapshot()
        assert done == {}
        assert b.status == {0: "failed", 1: "failed"}
        assert snap["counters"]["resilience.serve.failed_requests"] == 2

    def test_mca_off_engine_plain_retry_stays_ok(self, serve_setup):
        """A transient fault on an exact engine retries without claiming
        degradation (nothing was approximated away)."""
        cfg, model, params, eng = serve_setup
        b = ContinuousBatcher(eng)
        p = np.ones(4, np.int32)
        b.submit(Request(uid=0, prompt=p, max_new=2))
        with resilience.chaos(Fault("serve.prefill", mode="corrupt",
                                    times=1)):
            done = b.run()
        assert b.status[0] == "ok" and 0 in done


# ============================================== end-to-end observability ==
def test_recovery_counters_visible_in_scoped_snapshot(tmp_path, mca_setup):
    """Acceptance: a chaos run leaves a coherent resilience.* trail in one
    obs.scoped() snapshot spanning serve + train + checkpoint faults."""
    cfg, eng_on, _ = mca_setup
    with obs.scoped() as reg:
        b = ContinuousBatcher(eng_on)
        b.submit(Request(uid=0, prompt=np.ones(5, np.int32), max_new=2))
        with resilience.chaos(Fault("serve.prefill", mode="corrupt",
                                    times=1),
                              Fault("ckpt.write", mode="raise", times=1),
                              Fault("train.loss", mode="corrupt", times=1)):
            b.run()
            tr = _toy_trainer(tmp_path, total_steps=2, max_bad_steps=5)
            tr.run()
        snap = reg.snapshot()
    c = snap["counters"]
    assert c["resilience.injected.serve.prefill"] == 1
    assert c["resilience.serve.degraded_waves"] == 1
    assert c["train.skipped_steps"] == 1
    assert c["resilience.ckpt.write_failures"] == 1
    resil = {k for k in c if k.startswith("resilience.")}
    assert len(resil) >= 4


# ============================================ per-slot insertion chaos ==
class TestSlotBatcherChaos:
    def test_insert_corrupt_degrades_one_request(self, mca_setup):
        """A poisoned insertion retries exact for THAT request only: it
        finishes degraded and token-identical to an MCA-off engine; the
        other request stays ok."""
        from repro.serve import SlotBatcher
        cfg, eng_on, eng_off = mca_setup
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
                   for _ in range(2)]
        want0 = eng_off.generate(np.stack([prompts[0]] * 2),
                                 max_new=4)[0].tolist()
        b = SlotBatcher(eng_on, backoff_s=0.0)
        for uid, p in enumerate(prompts):
            b.submit(Request(uid=uid, prompt=p, max_new=4))
        with obs.scoped() as reg:
            with resilience.chaos(Fault("serve.insert", mode="corrupt",
                                        times=1)):
                done = b.run()
            snap = reg.snapshot()
        assert b.status[0] == "degraded" and b.status[1] == "ok"
        assert done[0] == want0, "exact retry must match the MCA-off engine"
        assert len(done[1]) == 4
        c = snap["counters"]
        assert c["resilience.serve.insert_retries"] == 1
        assert c["resilience.serve.degraded_requests"] == 1
        assert c["resilience.injected.serve.insert"] == 1

    def test_insert_persistent_fault_fails_only_requests(self, serve_setup):
        """serve.insert raising on every attempt fails the requests — the
        batcher never crashes and the engine stays usable."""
        from repro.serve import SlotBatcher
        cfg, model, params, eng = serve_setup
        b = SlotBatcher(eng, max_retries=1, backoff_s=0.0)
        p = np.ones(4, np.int32)
        b.submit(Request(uid=0, prompt=p, max_new=2))
        b.submit(Request(uid=1, prompt=p, max_new=2))
        with obs.scoped() as reg:
            with resilience.chaos(Fault("serve.insert", mode="raise",
                                        times=None)):
                done = b.run()
            snap = reg.snapshot()
        assert done == {}
        assert b.status == {0: "failed", 1: "failed"}
        assert snap["counters"]["resilience.serve.failed_requests"] == 2
        # engine still serves after the chaos plan is gone
        b2 = SlotBatcher(eng)
        b2.submit(Request(uid=2, prompt=p, max_new=2))
        assert len(b2.run()[2]) == 2

    def test_decode_fault_retries_burst(self, serve_setup):
        """A transient decode fault retries the burst; active chaos also
        forces K=1 so the fault surfaces at per-step granularity."""
        from repro.serve import SlotBatcher
        cfg, model, params, eng = serve_setup
        b = SlotBatcher(eng, backoff_s=0.0, check_every=8)
        p = np.ones(5, np.int32)
        b.submit(Request(uid=0, prompt=p, max_new=3))
        with obs.scoped() as reg:
            with resilience.chaos(Fault("serve.decode", mode="raise",
                                        times=1)):
                done = b.run()
            snap = reg.snapshot()
        assert b.status[0] == "ok" and len(done[0]) == 3
        assert snap["counters"]["resilience.serve.decode_retries"] == 1
