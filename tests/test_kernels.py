"""Per-kernel allclose tests vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import amm
from repro.kernels import (attn_colmax, flash_attention, mca_matmul,
                           mca_matmul_ragged)
from repro.kernels import ref as kref

jax.config.update("jax_platform_name", "cpu")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------- mca_matmul
@pytest.mark.parametrize("m,d,f,block,r", [
    (128, 512, 128, 128, 3),
    (256, 1024, 256, 128, 8),
    (128, 256, 384, 128, 1),
    (64, 256, 64, 64, 5),        # small blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mca_matmul_fixed_matches_ref(m, d, f, block, r, dtype):
    key = jax.random.PRNGKey(m + d + r)
    kx, kw, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (m, d), dtype=dtype)
    w = jax.random.normal(kw, (d, f), dtype=dtype)
    probs = amm.block_probs(w, block)
    idx, inv_rp = amm.draw_block_samples(ks, probs, r)
    out = mca_matmul(x, w, idx, inv_rp, block=block)
    ref = kref.ref_mca_matmul_fixed(x, w, idx, inv_rp, block)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_mca_matmul_fixed_matches_core_sampled_matmul():
    """Kernel == core estimator == unbiased AMM path used by the policy."""
    key = jax.random.PRNGKey(0)
    kx, kw, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (128, 512))
    w = jax.random.normal(kw, (512, 128))
    probs = amm.block_probs(w, 128)
    idx, inv_rp = amm.draw_block_samples(ks, probs, 4)
    out_kernel = mca_matmul(x, w, idx, inv_rp, block=128)
    out_core = amm.sampled_matmul(x, w, idx, inv_rp, block=128)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_core),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m,d,f,block,block_m,rmax", [
    (256, 512, 128, 128, 128, 4),
    (512, 1024, 256, 128, 128, 8),
])
def test_mca_matmul_ragged_matches_ref(m, d, f, block, block_m, rmax):
    key = jax.random.PRNGKey(7)
    kx, kw, kr, ks = jax.random.split(key, 4)
    x = jax.random.normal(kx, (m, d))
    w = jax.random.normal(kw, (d, f))
    m_tiles = m // block_m
    r_tile = jax.random.randint(kr, (m_tiles,), 1, rmax + 1)
    probs = amm.block_probs(w, block)
    idx = jax.random.categorical(ks, jnp.log(probs), shape=(m_tiles, rmax))
    inv_rp = 1.0 / (r_tile[:, None] * probs[idx])
    out = mca_matmul_ragged(x, w, r_tile, idx, inv_rp, block=block,
                            block_m=block_m)
    ref = kref.ref_mca_matmul_ragged(x, w, np.asarray(r_tile),
                                     idx, inv_rp, block, block_m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- flash_attention
@pytest.mark.parametrize("b,hq,hkv,sq,skv,dh", [
    (1, 2, 2, 128, 128, 64),       # MHA square
    (2, 4, 2, 128, 128, 64),       # GQA
    (1, 8, 1, 256, 256, 128),      # MQA
    (1, 2, 2, 128, 256, 64),       # cross / history (non-causal)
])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, hq, hkv, sq, skv, dh, causal, dtype):
    if causal and sq != skv:
        pytest.skip("causal offset covered by square cases")
    key = jax.random.PRNGKey(b * 100 + sq)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hq, sq, dh), dtype=dtype)
    k = jax.random.normal(kk, (b, hkv, skv, dh), dtype=dtype)
    v = jax.random.normal(kv, (b, hkv, skv, dh), dtype=dtype)
    scale = 1.0 / np.sqrt(dh)
    out, lse = flash_attention(q, k, v, scale=scale, causal=causal,
                               block_q=64, block_k=64)
    ref_out, ref_lse = kref.ref_attention(q, k, v, scale=scale, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-3, atol=1e-3)


# ----------------------------------------------------------- attn_colmax
@pytest.mark.parametrize("b,hq,hkv,s,dh", [
    (1, 2, 2, 128, 64),
    (2, 4, 2, 256, 64),
])
@pytest.mark.parametrize("causal", [False, True])
def test_attn_colmax_matches_ref(b, hq, hkv, s, dh, causal):
    key = jax.random.PRNGKey(s)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hq, s, dh))
    k = jax.random.normal(kk, (b, hkv, s, dh))
    v = jax.random.normal(kv, (b, hkv, s, dh))
    scale = 1.0 / np.sqrt(dh)
    _, lse = flash_attention(q, k, v, scale=scale, causal=causal,
                             block_q=64, block_k=64)
    cm = attn_colmax(q, k, lse, scale=scale, causal=causal, block_q=64,
                     block_k=64, reduce_heads=False)
    ref = kref.ref_colmax(q, k, lse, scale=scale, causal=causal)
    np.testing.assert_allclose(np.asarray(cm), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_colmax_is_valid_probability_mass(s=128):
    """colmax entries are in (0, 1] and every column someone attends to
    strongly is ~1 under a diagonal-dominant score matrix."""
    q = jnp.eye(s, 64)[None, None] * 10
    k = jnp.eye(s, 64)[None, None] * 10
    v = jnp.ones((1, 1, s, 64))
    _, lse = flash_attention(q, k, v, scale=1.0, causal=False)
    cm = attn_colmax(q, k, lse, scale=1.0, causal=False)
    assert float(cm.min()) > 0.0
    assert float(cm.max()) <= 1.0 + 1e-5
    assert float(cm[0, :64].min()) > 0.5  # diagonal keys dominate


def test_colmax_feeds_schedule_end_to_end():
    """flash lse -> colmax -> Eq.9 schedule produces sane r values."""
    from repro.core import schedule
    key = jax.random.PRNGKey(3)
    b, h, s, dh, d = 2, 4, 128, 64, 512
    q, k, v = (jax.random.normal(kk, (b, h, s, dh))
               for kk in jax.random.split(key, 3))
    scale = 1.0 / np.sqrt(dh)
    _, lse = flash_attention(q, k, v, scale=scale, causal=True)
    cm = attn_colmax(q, k, lse, scale=scale, causal=True)   # [B, S]
    r = schedule.r_cols_from_attention(cm, s, alpha=0.4, d=d)
    assert r.shape == (b, s)
    assert bool(jnp.all((r >= 1.0) & (r <= d)))


def test_tiered_dispatch_kernel_path_matches_jnp():
    """use_kernel=True (Pallas interpret) == jnp path inside the tiered
    dispatch (Mode-C integration; same RNG -> identical sample sets)."""
    from repro.core import dispatch
    key = jax.random.PRNGKey(0)
    kx, kw = jax.random.split(key)
    n, d, f, block = 256, 512, 128, 128
    x = jax.random.normal(kx, (n, d))
    w = jax.random.normal(kw, (d, f))
    tier = jnp.asarray([0, 1, 2, 3] * (n // 4), jnp.int32)
    imp = jnp.linspace(0, 1, n)
    ladder = (1, 2, 4, 4)
    caps = (n, n, n, n)
    y_ref = dispatch.tiered_mca_matmul(key, x, w, tier, imp, ladder, caps,
                                       block=block, use_kernel=False)
    y_ker = dispatch.tiered_mca_matmul(key, x, w, tier, imp, ladder, caps,
                                       block=block, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


# -------------------------------------------------------- kv_slot_update
@pytest.mark.parametrize("b,s,trail", [
    (4, 16, (2, 128)),      # GQA-shaped [B,S,hkv,dh]: whole tiles -> kernel
    (1, 8, (256,)),         # MLA latent [B,S,dl]: rows split a tile -> scatter
    (3, 12, (5, 7)),        # not lane-aligned -> scatter fallback
])
def test_kv_slot_update_per_row_write(b, s, trail):
    """Every layer of a 3-layer stack takes its own new row at each batch
    row's position, and nothing else moves."""
    from repro.kernels import kv_slot_update
    key = jax.random.PRNGKey(b * 100 + s)
    kc, kn = jax.random.split(key)
    cache = jax.random.normal(kc, (3, b, s) + trail)
    new = jax.random.normal(kn, (3, b, 1) + trail)
    pos = jnp.asarray([(3 * i + 1) % s for i in range(b)], jnp.int32)
    out = kv_slot_update(cache, new, pos)
    ref = cache.at[:, jnp.arange(b), pos].set(new[:, :, 0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=0)


def test_kv_slot_update_dispatch_counters():
    """Rows that are whole tiles take the Pallas kernel; others fall back
    to the XLA scatter — recorded at dispatch time in repro.obs."""
    from repro import obs
    from repro.kernels import kv_slot_update
    with obs.scoped() as reg:
        kv_slot_update(jnp.zeros((2, 2, 4, 1, 128)),
                       jnp.ones((2, 2, 1, 1, 128)), jnp.zeros(2, jnp.int32))
        kv_slot_update(jnp.zeros((2, 2, 4, 5)), jnp.ones((2, 2, 1, 5)),
                       jnp.zeros(2, jnp.int32))
        snap = reg.snapshot()
    c = snap["counters"]
    assert c["kernels.kv_slot_update.kernel_calls"] == 1
    assert c["kernels.kv_slot_update.fallback_calls"] == 1


# ------------------------------------------------------ decode_attention
@pytest.mark.parametrize("block_b,block_n", [(4, 256), (2, 128), (1, 64)])
def test_decode_attention_matches_softmax(block_b, block_n):
    """The stacked-cache decode kernel equals a materialized softmax over
    the current token plus the valid rows of its own kv head, in layer 1
    of a 3-layer stack, whatever its blocking (one block, or an online
    softmax across several)."""
    from repro.kernels.decode_attention import decode_attention
    l, b, slots, hkv, g, dh = 3, 4, 128, 2, 3, 128
    n, r = slots * hkv, hkv * g
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    q = jax.random.normal(ks[0], (b, r, dh))
    k = jax.random.normal(ks[1], (l, b, n, dh))
    v = jax.random.normal(ks[2], (l, b, n, dh))
    valid = jax.random.bernoulli(ks[3], 0.7, (b, 1, n)).astype(jnp.int32)
    s1 = jax.random.normal(ks[4], (b, r, 1))
    v1 = jax.random.normal(ks[5], (b, r, dh))
    scale = dh ** -0.5
    out, den = decode_attention(q, k, v, valid, s1, v1, jnp.int32(1),
                                scale=scale, hkv=hkv, block_b=block_b,
                                block_n=block_n, interpret=True)
    s = jnp.einsum("brd,bnd->brn", q, k[1]) * scale
    own = (jnp.arange(r)[:, None] // g) == (jnp.arange(n)[None] % hkv)
    s = jnp.where(own & (valid != 0), s, kref.NEG_INF)
    a = jax.nn.softmax(jnp.concatenate([s1, s], axis=-1), axis=-1)
    want = a[..., :1] * v1 + jnp.einsum("brn,bnd->brd", a[..., 1:], v[1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               **_tol(jnp.float32))
    # a head's largest attention probability is 1 / den
    np.testing.assert_allclose(np.asarray(1.0 / den[..., 0]),
                               np.asarray(jnp.max(a, axis=-1)),
                               **_tol(jnp.float32))
    ref_out, ref_den = kref.ref_decode_attention(
        q, k, v, valid, s1, v1, jnp.int32(1), scale=scale, hkv=hkv,
        chunk=block_n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               **_tol(jnp.float32))
    np.testing.assert_allclose(np.asarray(den), np.asarray(ref_den),
                               **_tol(jnp.float32))


def test_decode_attention_dispatch():
    """Whole-lane head dims take the kernel, others the jnp fallback —
    recorded at dispatch time in repro.obs."""
    from repro import obs
    from repro.kernels.decode_attention import pick_blocks
    from repro.kernels.ops import decode_attention
    assert pick_blocks(64, 2048, 128, 2) == (4, 2048)
    assert pick_blocks(16, 8448, 128, 2) == (1, 4224)
    assert pick_blocks(4, 64, 32, 4) is None
    with obs.scoped() as reg:
        for dh in (128, 32):
            decode_attention(jnp.zeros((2, 2, dh)), jnp.zeros((1, 2, 8, dh)),
                             jnp.zeros((1, 2, 8, dh)),
                             jnp.ones((2, 1, 8), jnp.int32),
                             jnp.zeros((2, 2, 1)), jnp.zeros((2, 2, dh)),
                             jnp.int32(0), scale=1.0, hkv=2)
        c = reg.snapshot()["counters"]
    assert c["kernels.decode_attention.kernel_calls"] == 1
    assert c["kernels.decode_attention.fallback_calls"] == 1


# ------------------------------------------------------ device telemetry
class TestDeviceTelemetry:
    """Per-execution launch counts (repro.obs.devtel) — distinct from the
    dispatch-time kernel_calls/fallback_calls counters: a jitted K-step
    decode scan is ONE traced call site but K device launches.  Telemetry
    is a trace-time flag, so every test compiles fresh functions under
    ``devtel.enabled_scope()``."""

    def _deltas(self, fn):
        from repro.obs import devtel
        base = devtel.totals()
        jax.block_until_ready(fn())
        devtel.sync()
        return devtel.since(base)

    def test_kv_update_scan_counts_every_launch_kernel_path(self):
        from repro.kernels import kv_slot_update
        from repro.obs import devtel
        n_layers, b, s, f, steps = 2, 2, 16, 128, 5
        with devtel.enabled_scope():
            @jax.jit
            def burst(cache, new, pos):
                def body(c, i):
                    return kv_slot_update(c, new, pos + i), ()
                return jax.lax.scan(body, cache, jnp.arange(steps))[0]
            d = self._deltas(lambda: burst(
                jnp.zeros((n_layers, b, s, 1, f)),
                jnp.ones((n_layers, b, 1, 1, f)), jnp.zeros(b, jnp.int32)))
        assert d["kernels.kv_slot_update.device_launches"] == steps
        assert (d["kernels.kv_slot_update.device_rows_written"]
                == steps * n_layers * b)

    def test_kv_update_scan_counts_every_launch_fallback_path(self):
        from repro.kernels import kv_slot_update
        from repro.obs import devtel
        n_layers, b, s, f, steps = 2, 3, 16, 96, 4   # f % 128 -> scatter
        with devtel.enabled_scope():
            @jax.jit
            def burst(cache, new, pos):
                def body(c, i):
                    return kv_slot_update(c, new, pos + i), ()
                return jax.lax.scan(body, cache, jnp.arange(steps))[0]
            d = self._deltas(lambda: burst(
                jnp.zeros((n_layers, b, s, f)),
                jnp.ones((n_layers, b, 1, f)), jnp.zeros(b, jnp.int32)))
        assert d["kernels.kv_slot_update.device_launches"] == steps
        assert (d["kernels.kv_slot_update.device_rows_written"]
                == steps * n_layers * b)

    def test_mca_fixed_sampled_blocks_kernel_path(self):
        from repro.obs import devtel
        key = jax.random.PRNGKey(3)
        kx, kw, ks = jax.random.split(key, 3)
        x = jax.random.normal(kx, (256, 512))   # 2 row tiles of 128
        w = jax.random.normal(kw, (512, 128))
        probs = amm.block_probs(w, 128)
        idx, inv_rp = amm.draw_block_samples(ks, probs, 3)
        with devtel.enabled_scope():
            d = self._deltas(lambda: mca_matmul(x, w, idx, inv_rp,
                                                block=128))
        assert d["kernels.mca_matmul.device_launches"] == 1
        # kernel accumulates one count per (row tile, sample): 2 * 3
        assert d["kernels.mca_matmul.device_sampled_blocks"] == 6

    def test_mca_fixed_sampled_blocks_fallback_path(self):
        from repro.obs import devtel
        key = jax.random.PRNGKey(4)
        kx, kw, ks = jax.random.split(key, 3)
        x = jax.random.normal(kx, (200, 512))   # 200 % 128 != 0 -> ref
        w = jax.random.normal(kw, (512, 128))
        probs = amm.block_probs(w, 128)
        idx, inv_rp = amm.draw_block_samples(ks, probs, 3)
        with devtel.enabled_scope():
            d = self._deltas(lambda: mca_matmul(x, w, idx, inv_rp,
                                                block=128))
        assert d["kernels.mca_matmul.device_launches"] == 1
        # dense fallback has no row tiling: counts the sample list length
        assert d["kernels.mca_matmul.device_sampled_blocks"] == 3

    def test_mca_ragged_counts_accumulated_blocks_only(self):
        from repro.obs import devtel
        key = jax.random.PRNGKey(5)
        kx, kw, ks = jax.random.split(key, 3)
        m, d_, f, block, rmax = 256, 512, 128, 128, 4
        x = jax.random.normal(kx, (m, d_))
        w = jax.random.normal(kw, (d_, f))
        r_tile = jnp.asarray([1, 3], jnp.int32)  # 2 row tiles
        probs = amm.block_probs(w, block)
        idx = jax.random.categorical(ks, jnp.log(probs), shape=(2, rmax))
        inv_rp = 1.0 / (r_tile[:, None] * probs[idx])
        with devtel.enabled_scope():
            dl = self._deltas(lambda: mca_matmul_ragged(
                x, w, r_tile, idx, inv_rp, block=block, block_m=128))
        assert dl["kernels.mca_matmul_ragged.device_launches"] == 1
        # pl.when skips samples past r_tile[t]: only sum(r_tile) counted
        assert dl["kernels.mca_matmul_ragged.device_sampled_blocks"] == 4

    def test_flash_attention_counts_causal_tiles(self):
        from repro.obs import devtel
        key = jax.random.PRNGKey(6)
        kq, kk, kv = jax.random.split(key, 3)
        b, h, s, dh = 1, 2, 192, 64             # 3x3 tile grid at 64
        q = jax.random.normal(kq, (b, h, s, dh))
        k = jax.random.normal(kk, (b, h, s, dh))
        v = jax.random.normal(kv, (b, h, s, dh))
        with devtel.enabled_scope():
            d = self._deltas(lambda: flash_attention(
                q, k, v, scale=0.125, causal=True, block_q=64, block_k=64))
        assert d["kernels.flash_attention.device_launches"] == 1
        # causal skips strictly-upper tiles: b*h*6 of 9 computed
        assert d["kernels.flash_attention.device_tiles"] == b * h * 6

    def test_attn_colmax_counts_tiles_and_matches_flash(self):
        from repro.obs import devtel
        key = jax.random.PRNGKey(7)
        kq, kk, kv = jax.random.split(key, 3)
        b, h, s, dh = 1, 2, 192, 64
        q = jax.random.normal(kq, (b, h, s, dh))
        k = jax.random.normal(kk, (b, h, s, dh))
        v = jax.random.normal(kv, (b, h, s, dh))
        with devtel.enabled_scope():
            _, lse = flash_attention(q, k, v, scale=0.125, causal=True,
                                     block_q=64, block_k=64)
            d = self._deltas(lambda: attn_colmax(
                q, k, lse, scale=0.125, causal=True, block_q=64,
                block_k=64))
        assert d["kernels.attn_colmax.device_launches"] == 1
        assert d["kernels.attn_colmax.device_tiles"] == b * h * 6

    def test_disabled_emits_nothing(self):
        from repro.obs import devtel
        key = jax.random.PRNGKey(8)
        kx, kw, ks = jax.random.split(key, 3)
        x = jax.random.normal(kx, (128, 256))
        w = jax.random.normal(kw, (256, 128))
        probs = amm.block_probs(w, 128)
        idx, inv_rp = amm.draw_block_samples(ks, probs, 2)
        assert not devtel.enabled()
        d = self._deltas(lambda: mca_matmul(x, w, idx, inv_rp, block=128))
        assert not any(k.startswith("kernels.mca_matmul.device")
                       for k in d)

    def test_device_tier_hist_matches_stats_pytree(self):
        """The per-execution mca.device_tier_hist.t{i} totals must agree
        with the stats-pytree tier_hist the host reads once per step."""
        from repro.core.policy import MCAConfig, mca_project
        from repro.obs import devtel
        cfg = MCAConfig(enabled=True, alpha=0.4, block=16,
                        sites=("v_proj",))
        n, dm, f = 64, 64, 32
        key = jax.random.PRNGKey(9)
        kx, kw, ki = jax.random.split(key, 3)
        x = jax.random.normal(kx, (n, dm))
        w = jax.random.normal(kw, (dm, f))
        imp = jnp.abs(jax.random.normal(ki, (n,)))
        with devtel.enabled_scope():
            @jax.jit
            def run(key):
                _, stats = mca_project(key, x, w, imp, seq_len=n, cfg=cfg,
                                       site="v_proj")
                return stats["tier_hist"]
            base = devtel.totals()
            hist = np.asarray(run(jax.random.PRNGKey(10)))
            devtel.sync()
            deltas = devtel.since(base)
        assert int(hist.sum()) == n
        for i, hv in enumerate(hist):
            assert deltas.get(f"mca.device_tier_hist.t{i}", 0.0) == float(hv)

    def test_registry_snapshot_windows_device_totals(self):
        """Registries only see devtel activity since their creation, so
        scoped() collection stays isolated despite the global store."""
        from repro import obs
        from repro.obs import devtel
        b, s, f = 4, 8, 128
        with devtel.enabled_scope():
            @jax.jit
            def one(cache, new, pos):
                from repro.kernels import kv_slot_update
                return kv_slot_update(cache, new, pos)
            args = (jnp.zeros((1, b, s, 1, f)), jnp.ones((1, b, 1, 1, f)),
                    jnp.zeros(b, jnp.int32))
            jax.block_until_ready(one(*args))    # activity BEFORE scope
            devtel.sync()
            with obs.scoped() as reg:
                jax.block_until_ready(one(*args))
                devtel.sync()
                snap = reg.snapshot()
        c = snap["counters"]
        assert c["kernels.kv_slot_update.device_launches"] == 1
        assert c["kernels.kv_slot_update.device_rows_written"] == b
