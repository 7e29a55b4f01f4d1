"""Multi-device SPMD tests — run in a subprocess with 8 forced host devices
(the main pytest process must keep seeing 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import build_model, reduced
    from repro.dist import context as dctx, sharding as shd
    from repro.optim import adamw
    from repro.train.step import (abstract_state, jit_train_step,
                                  make_train_step, train_step_shardings)
    from repro.data import SyntheticLM

    assert jax.device_count() == 8, jax.device_count()
    mesh = make_mesh((2, 4))

    cfg = reduced(get_config("qwen3-32b"), n_layers=2, d_model=64,
                  n_heads=8, n_kv_heads=4, d_head=16, d_ff=128,
                  vocab_size=256)
    model = build_model(cfg)
    data = SyntheticLM(cfg.vocab_size, 16, 4, seed=0)
    batch = jax.tree.map(jnp.asarray, data.batch(0))

    with dctx.use_mesh(mesh):
        step = jit_train_step(mesh, model, adamw.AdamWConfig(lr=1e-3),
                              jax.eval_shape(lambda: batch), donate=False)
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw.init_state(params)
        in_sh, _ = train_step_shardings(mesh, model, batch)
        params = jax.device_put(params, in_sh[0])
        opt = jax.device_put(opt, in_sh[1])
        batch = jax.device_put(batch, in_sh[2])
        p2, o2, m = step(params, opt, batch)
        loss0 = float(m["total_loss"])
        p3, o3, m = step(p2, o2, batch)
        loss1 = float(m["total_loss"])
    assert np.isfinite(loss0) and np.isfinite(loss1)
    assert loss1 < loss0, (loss0, loss1)

    # verify TP sharding actually applied: ffn w_up sharded over model
    w_up_sh = p2["layers"]["ffn"]["w_up"].sharding
    assert "model" in str(w_up_sh.spec), w_up_sh.spec
    # ZeRO-1: adam moments sharded over data too
    m_sh = o2["m"]["layers"]["ffn"]["w_up"].sharding
    assert "data" in str(m_sh.spec), m_sh.spec
    print("OK losses", loss0, loss1)
""")

_LAUNCH_TRAIN_SCRIPT = textwrap.dedent("""
    # launch.train.train on a --mesh 2,4 layout: the first-step loss matches
    # the one-device run, the training state is created split over the
    # mesh, and the jitted step compiles once across steps.
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_mesh, parse_mesh
    from repro.launch.train import train
    from repro.models import build_model, reduced
    from repro.optim import adamw
    from repro.train import TrainerConfig

    cfg = reduced(get_config("starcoder2-3b"), dtype="float32")
    model = build_model(cfg)
    data = SyntheticLM(cfg.vocab_size, 32, 4, seed=0)
    tcfg = TrainerConfig(total_steps=3, log_every=1, finite_checks=False)
    opt = adamw.AdamWConfig(lr=1e-3)
    one = train(model, opt, data, tcfg)[1]["history"][0]["loss"]
    mesh = make_mesh(parse_mesh("2,4", jax.device_count()))
    trainer, out = train(model, opt, data, tcfg, mesh=mesh)
    losses = [h["loss"] for h in out["history"]]
    assert len(losses) == 3 and np.all(np.isfinite(losses)), losses
    assert abs(losses[0] - one) <= 1e-5 * abs(one), (losses[0], one)
    assert trainer.train_step._cache_size() == 1, "step compiled twice"
    w = trainer.params["layers"]["ffn"]["w_up"]
    m = trainer.opt_state["m"]["layers"]["ffn"]["w_up"]
    for a in (w, m):
        assert a.addressable_shards[0].data.size * 4 <= a.size, a.sharding
    print("OK launch train", one, losses)
""")

_MCA_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.core.policy import MCAConfig
    from repro.launch.mesh import make_mesh
    from repro.models import build_model, reduced
    from repro.dist import context as dctx, sharding as shd
    from repro.data import SyntheticLM

    mesh = make_mesh((2, 4))
    cfg = reduced(get_config("qwen3-32b"), n_layers=2, d_model=64,
                  n_heads=8, n_kv_heads=4, d_head=16, d_ff=128,
                  vocab_size=256,
                  mca=MCAConfig(enabled=True, alpha=0.4, block=16,
                                sites=("v_proj",)))
    model = build_model(cfg)
    data = SyntheticLM(cfg.vocab_size, 16, 4, seed=0)
    batch = jax.tree.map(jnp.asarray, data.batch(0))
    with dctx.use_mesh(mesh):
        a_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        p_sh = shd.param_shardings(mesh, a_params, cfg)
        b_sh = shd.batch_shardings(mesh, batch)
        params = jax.device_put(model.init(jax.random.PRNGKey(0)), p_sh)
        batch = jax.device_put(batch, b_sh)
        loss, metrics = jax.jit(
            lambda p, b: model.loss(p, b, jax.random.PRNGKey(1)))(
                params, batch)
        assert np.isfinite(float(loss))
        assert float(metrics["mca_flops"]) > 0
    print("OK mca sharded loss", float(loss))
""")


_SHARD_SAMPLING_SCRIPT = textwrap.dedent("""
    # Regression: the PRNG key enters _tiered_maybe_sharded's shard_map
    # replicated, so every shard used to draw IDENTICAL block samples —
    # estimator errors were perfectly correlated along the token axis and
    # variance did not shrink with mesh size.  With the axis_index fold-in,
    # duplicated rows on different shards must draw different samples and
    # averaging the two shard estimates must reduce the error.
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.policy import MCAConfig, mca_project
    from repro.dist import context as dctx
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2,), ("data",))
    n, d, f = 16, 256, 64
    half = n // 2
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x_half = jax.random.normal(kx, (half, d))
    x = jnp.concatenate([x_half, x_half])      # shard 1 duplicates shard 0
    w = jax.random.normal(kw, (d, f))
    imp = jnp.full((n,), 0.5)
    exact = np.asarray(x_half @ w)
    cfg = MCAConfig(enabled=True, alpha=0.3, block=16, mode="tiered",
                    sites=("v_proj",))

    diffs, mse_half, mse_comb = [], [], []
    with dctx.use_mesh(mesh):
        for t in range(6):
            y, stats = mca_project(jax.random.PRNGKey(100 + t), x, w,
                                   imp, 64, cfg, "v_proj")
            y = np.asarray(y)
            assert float(stats["mca_flops"]) < float(stats["exact_flops"]), \\
                "schedule did not sample; test vacuous"
            diffs.append(float(np.abs(y[:half] - y[half:]).max()))
            mse_half.append(float(((y[:half] - exact) ** 2).mean()))
            comb = (y[:half] + y[half:]) / 2.0
            mse_comb.append(float(((comb - exact) ** 2).mean()))

    # identical rows on different shards -> different draws
    assert min(diffs) > 1e-6, f"shards drew identical samples: {diffs}"
    # independent draws: averaging the shard estimates cuts the MSE
    mh, mc = np.mean(mse_half), np.mean(mse_comb)
    assert mc < 0.75 * mh, (mh, mc)
    print("OK shard sampling", mh, mc)
""")


_PSUM_SNAPSHOT_SCRIPT = textwrap.dedent("""
    # SPMD-aggregated obs snapshots: additive leaves psum across the world
    # without double counting (each local replica carries 1/n_local), min/
    # max combine with pmin/pmax, empty-histogram nan does not poison them,
    # and repeated aggregated snapshots see identical totals.
    import math
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro import obs
    from repro.obs import devtel

    assert jax.device_count() == 8
    reg = obs.Registry()
    with obs.scoped(reg):
        reg.counter("a").inc(3)
        reg.counter("b").inc(0.5)
        h = reg.histogram("lat_seconds")
        for v in (1.0, 2.0, 5.0):
            h.observe(v)
        reg.histogram("empty")
        agg = obs.snapshot(aggregate="psum")

    assert agg["counters"]["a"] == 3.0, agg["counters"]
    assert agg["counters"]["b"] == 0.5, agg["counters"]
    hh = agg["histograms"]["lat_seconds"]
    assert hh["count"] == 3.0 and hh["sum"] == 8.0, hh
    assert abs(hh["mean"] - 8.0 / 3.0) < 1e-6, hh
    assert hh["min"] == 1.0 and hh["max"] == 5.0, hh
    he = agg["histograms"]["empty"]
    assert he["count"] == 0.0, he
    assert math.isnan(he["min"]) and math.isnan(he["max"]), he
    agg2 = obs.snapshot(aggregate="psum", registry=reg)
    assert agg2["counters"] == agg["counters"]
    assert agg2["histograms"] == agg["histograms"]

    # device telemetry emitted under pmap: one callback per device, all
    # eight land in the same process-global store and the snapshot merge
    devtel.enable(True)

    @jax.pmap
    def f(x):
        devtel.emit("spmd.launches", 1.0)
        return x * 2

    jax.block_until_ready(f(jnp.arange(8.0)))
    devtel.sync()
    snap = reg.snapshot()
    assert snap["counters"]["spmd.launches"] == 8.0, snap["counters"]
    print("OK psum snapshot")
""")


def _run(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@pytest.mark.slow
def test_sharded_train_step_8dev():
    out = _run(_SCRIPT)
    assert "OK losses" in out


@pytest.mark.slow
def test_launch_train_mesh_8dev():
    out = _run(_LAUNCH_TRAIN_SCRIPT)
    assert "OK launch train" in out


@pytest.mark.slow
def test_mca_under_spmd_8dev():
    out = _run(_MCA_SCRIPT)
    assert "OK mca sharded" in out


@pytest.mark.slow
def test_sharded_sampling_independent_across_shards():
    out = _run(_SHARD_SAMPLING_SCRIPT)
    assert "OK shard sampling" in out


@pytest.mark.slow
def test_psum_snapshot_8dev():
    out = _run(_PSUM_SNAPSHOT_SCRIPT)
    assert "OK psum snapshot" in out
