"""Substrate tests: optimizer, data pipeline, checkpointing, trainer
fault-tolerance, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_config
from repro.data import MemmapLM, Prefetcher, SyntheticLM, write_token_file
from repro.dist import compress
from repro.launch.mesh import make_mesh
from repro.models import build_model, reduced
from repro.optim import adamw
from repro.train import Trainer, TrainerConfig, make_train_step

jax.config.update("jax_platform_name", "cpu")


class TestAdamW:
    def test_quadratic_convergence(self):
        cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, clip_norm=10.0)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = adamw.init_state(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state, _ = adamw.apply_updates(cfg, params, grads, state)
        assert float(jnp.abs(params["w"]).max()) < 1e-2

    def test_clip_norm(self):
        grads = {"a": jnp.asarray([3.0, 4.0])}
        clipped, norm = adamw.clip_by_global_norm(grads, 1.0)
        np.testing.assert_allclose(float(norm), 5.0, rtol=1e-6)
        np.testing.assert_allclose(
            float(adamw.global_norm(clipped)), 1.0, rtol=1e-5)

    def test_schedule_warmup_and_decay(self):
        sch = adamw.cosine_schedule(warmup=10, total=100)
        assert float(sch(jnp.asarray(5))) == pytest.approx(0.5, abs=1e-6)
        assert float(sch(jnp.asarray(10))) == pytest.approx(1.0, abs=1e-6)
        assert float(sch(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-3)

    def test_accumulation_matches_full_batch(self):
        cfg = reduced(get_config("starcoder2-3b"), n_layers=1)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        data = SyntheticLM(cfg.vocab_size, 16, 8, seed=1)
        batch = jax.tree.map(jnp.asarray, data.batch(0))

        def loss_fn(p, b, k):
            return model.loss(p, b, None)

        (l1, _), g1 = adamw.accumulate_gradients(loss_fn, params, batch, 1)
        (l4, _), g4 = adamw.accumulate_gradients(loss_fn, params, batch, 4)
        np.testing.assert_allclose(float(l1), float(l4), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-3, atol=2e-5)


class TestData:
    def test_deterministic_replay(self):
        d1 = SyntheticLM(100, 32, 8, seed=7)
        d2 = SyntheticLM(100, 32, 8, seed=7)
        b1, b2 = d1.batch(5), d2.batch(5)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_host_sharding_disjoint(self):
        full = SyntheticLM(100, 16, 8, seed=3, n_hosts=1, host_id=0)
        h0 = SyntheticLM(100, 16, 8, seed=3, n_hosts=2, host_id=0)
        h1 = SyntheticLM(100, 16, 8, seed=3, n_hosts=2, host_id=1)
        assert h0.batch(0)["tokens"].shape[0] == 4
        assert not np.array_equal(h0.batch(0)["tokens"],
                                  h1.batch(0)["tokens"])
        assert full.batch(0)["tokens"].shape[0] == 8

    def test_labels_are_next_tokens(self):
        d = SyntheticLM(100, 16, 4, seed=0)
        b = d.batch(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_memmap_roundtrip(self, tmp_path):
        path = str(tmp_path / "tokens.bin")
        write_token_file(path, np.arange(10_000) % 97)
        d = MemmapLM(path, 97, 32, 4, seed=0)
        b = d.batch(3)
        assert b["tokens"].shape == (4, 32)
        assert b["tokens"].max() < 97
        b2 = MemmapLM(path, 97, 32, 4, seed=0).batch(3)
        np.testing.assert_array_equal(b["tokens"], b2["tokens"])

    def test_prefetcher(self):
        d = SyntheticLM(50, 8, 2, seed=0)
        pf = Prefetcher(d, depth=2)
        s0, b0 = pf.next()
        s1, b1 = pf.next()
        assert (s0, s1) == (0, 1)
        np.testing.assert_array_equal(b0["tokens"], d.batch(0)["tokens"])
        pf.close()


class TestCheckpoint:
    def _tree(self):
        return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": {"c": jnp.ones((4,), jnp.bfloat16)}}

    def test_save_restore_roundtrip(self, tmp_path):
        tree = self._tree()
        ckpt.save(str(tmp_path), 3, tree)
        out = ckpt.restore(str(tmp_path), 3, jax.eval_shape(lambda: tree))
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_latest_and_gc(self, tmp_path):
        tree = self._tree()
        for s in (1, 2, 3, 4, 5):
            ckpt.save(str(tmp_path), s, tree, keep=2)
        assert ckpt.latest_step(str(tmp_path)) == 5
        steps = sorted(os.listdir(tmp_path))
        assert len(steps) == 2

    def test_structure_mismatch_raises(self, tmp_path):
        ckpt.save(str(tmp_path), 1, self._tree())
        with pytest.raises(ckpt.StructureMismatchError,
                           match="structure mismatch"):
            ckpt.restore(str(tmp_path), 1, {"x": jnp.zeros((2,))})

    def test_async_checkpointer(self, tmp_path):
        c = ckpt.AsyncCheckpointer(str(tmp_path))
        c.save(7, self._tree())
        c.wait()
        assert ckpt.latest_step(str(tmp_path)) == 7

    def test_elastic_restore_with_shardings(self, tmp_path):
        """Restore onto explicit (1-device) shardings — the reshard path."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = make_mesh((1, 1))
        tree = self._tree()
        ckpt.save(str(tmp_path), 1, tree)
        sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
        out = ckpt.restore(str(tmp_path), 1, jax.eval_shape(lambda: tree),
                           shardings=sh)
        assert out["a"].sharding == NamedSharding(mesh, P())


class TestTrainerFaultTolerance:
    def _setup(self, tmp_path, total_steps=6):
        cfg = reduced(get_config("starcoder2-3b"), n_layers=1,
                      vocab_size=128)
        model = build_model(cfg)
        data = SyntheticLM(cfg.vocab_size, 16, 4, seed=0)
        opt = adamw.AdamWConfig(lr=1e-3)
        step = jax.jit(make_train_step(model, opt))
        tcfg = TrainerConfig(total_steps=total_steps,
                             ckpt_dir=str(tmp_path / "ckpt"),
                             ckpt_every=2, log_every=100, watchdog_s=600)
        return model, opt, data, step, tcfg

    def test_loss_decreases(self, tmp_path):
        model, opt, data, step, tcfg = self._setup(tmp_path, total_steps=30)
        tr = Trainer(model, opt, data, step, tcfg)
        out = tr.run()
        first = np.mean([h["loss"] for h in out["history"][:5]])
        last = np.mean([h["loss"] for h in out["history"][-5:]])
        assert last < first, (first, last)

    def test_restart_resumes_exactly(self, tmp_path):
        """Kill after 6 steps, restart, verify identical final params to an
        uninterrupted 12-step run (deterministic data replay + ckpt)."""
        model, opt, data, step, tcfg = self._setup(tmp_path)
        tr1 = Trainer(model, opt, data, step, tcfg)      # runs 0..6
        tr1.run()
        tcfg2 = TrainerConfig(**{**tcfg.__dict__, "total_steps": 12})
        tr2 = Trainer(model, opt, data, step, tcfg2)     # resumes at 6
        assert tr2.start_step == 6
        out2 = tr2.run()
        assert out2["steps"] == 6

        # uninterrupted reference
        import shutil
        shutil.rmtree(tcfg.ckpt_dir)
        tr3 = Trainer(model, opt, data, step, tcfg2)
        assert tr3.start_step == 0
        tr3.run()
        for a, b in zip(jax.tree.leaves(tr2.params),
                        jax.tree.leaves(tr3.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-5, atol=1e-6)

    def test_watchdog_fires_on_slow_step(self, tmp_path):
        import time
        model, opt, data, _, tcfg = self._setup(tmp_path, total_steps=1)
        tcfg.watchdog_s = 0.05

        def slow_step(params, opt_state, batch):
            time.sleep(0.2)
            opt_state = dict(opt_state)
            opt_state["count"] = opt_state["count"] + 1
            return params, opt_state, {"total_loss": jnp.zeros(())}

        tr = Trainer(model, opt, data, slow_step, tcfg)
        out = tr.run()
        assert out["watchdog_fired"] >= 1


class TestGradCompression:
    def test_quantize_roundtrip_error_small(self):
        g = jax.random.normal(jax.random.PRNGKey(0), (128,))
        q, s = compress.quantize(g)
        deq = compress.dequantize(q, s)
        rel = float(jnp.linalg.norm(deq - g) / jnp.linalg.norm(g))
        assert rel < 0.01

    def test_error_feedback_telescopes(self):
        """Sum of dequantized grads + final residual == sum of true grads
        (EF makes compression unbiased over time)."""
        key = jax.random.PRNGKey(1)
        grads = [jax.random.normal(jax.random.fold_in(key, i), (64,)) * 0.1
                 for i in range(20)]
        tree = {"w": jnp.zeros((64,))}
        err = compress.init_error_buffer(tree)
        total_sent = jnp.zeros((64,))
        for g in grads:
            q, s, err = compress.ef_compress_tree({"w": g}, err)
            total_sent = total_sent + compress.dequantize(q["w"], s["w"])
        true_sum = sum(grads)
        resid = err["w"]
        np.testing.assert_allclose(np.asarray(total_sent + resid),
                                   np.asarray(true_sum), rtol=1e-4,
                                   atol=1e-5)

    def test_shard_map_psum_compressed(self):
        """psum_compressed under shard_map on a 1-device mesh."""
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh((1,), ("data",))
        g = {"w": jnp.ones((8,))}
        e = compress.init_error_buffer(g)

        def f(g, e):
            return compress.psum_compressed(g, e, "data")

        out, new_e = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                   out_specs=(P(), P()))(g, e)
        np.testing.assert_allclose(np.asarray(out["w"]), 1.0, rtol=1e-2)
