"""Regression tests for kernel dispatch (repro.kernels.ops).

Covers three bugs:
  * flash_attention / attn_colmax dropped the causal *diagonal offset* for
    rectangular (sq < skv) shapes — decode-style suffix queries attended to
    the wrong triangle;
  * mca_matmul_ragged crashed (kernel-side assert) whenever the row-tile
    count implied a tile size below block_m, instead of falling back;
  * the wrappers passed the caller's block sizes through unclamped, so the
    dispatch decision and the kernel's own clamping could disagree.

Also checks that every dispatch records kernel/fallback counters in the
repro.obs registry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import amm
from repro.kernels import (attn_colmax, flash_attention, mca_matmul,
                           mca_matmul_ragged)
from repro.kernels import ref as kref

jax.config.update("jax_platform_name", "cpu")


def _qkv(b, hq, hkv, sq, skv, dh, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, hq, sq, dh))
    k = jax.random.normal(kk, (b, hkv, skv, dh))
    v = jax.random.normal(kv, (b, hkv, skv, dh))
    return q, k, v


# --------------------------------------------- causal offset (sq < skv)
@pytest.mark.parametrize("sq,skv", [(64, 128), (64, 192), (128, 256)])
def test_flash_attention_causal_rectangular(sq, skv):
    """Suffix queries (kv history longer than the query span) must mask
    against the shifted diagonal, matching the reference oracle."""
    q, k, v = _qkv(1, 2, 2, sq, skv, 32)
    scale = 1.0 / np.sqrt(32)
    out, lse = flash_attention(q, k, v, scale=scale, causal=True,
                               block_q=64, block_k=64)
    ref_out, ref_lse = kref.ref_attention(q, k, v, scale=scale, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("sq,skv", [(64, 128), (128, 256)])
def test_attn_colmax_causal_rectangular(sq, skv):
    q, k, v = _qkv(1, 2, 2, sq, skv, 32, seed=1)
    scale = 1.0 / np.sqrt(32)
    _, lse = kref.ref_attention(q, k, v, scale=scale, causal=True)
    cm = attn_colmax(q, k, lse, scale=scale, causal=True,
                     block_q=64, block_k=64)
    ref_cm = jnp.max(kref.ref_colmax(q, k, lse, scale=scale, causal=True),
                     axis=1)
    np.testing.assert_allclose(np.asarray(cm), np.asarray(ref_cm),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------- ragged tile fallback
def test_mca_matmul_ragged_small_row_tiles():
    """m=192 with 3 row tiles implies bm=64 < block_m=128: must not crash
    and must match the eager reference."""
    m, d, f, block, rmax = 192, 256, 128, 64, 3
    kx, kw, kr, ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(kx, (m, d))
    w = jax.random.normal(kw, (d, f))
    m_tiles = 3
    r_tile = jax.random.randint(kr, (m_tiles,), 1, rmax + 1)
    probs = amm.block_probs(w, block)
    idx = jax.random.categorical(ks, jnp.log(probs), shape=(m_tiles, rmax))
    inv_rp = 1.0 / (r_tile[:, None] * probs[idx])
    out = mca_matmul_ragged(x, w, r_tile, idx, inv_rp, block=block,
                            block_m=128)
    ref = kref.ref_mca_matmul_ragged(x, w, np.asarray(r_tile), idx, inv_rp,
                                     block, m // m_tiles)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_mca_matmul_ragged_fallback_traceable_under_jit():
    """The fallback path must not concretize r_tile (jit-safe)."""
    m, d, f, block = 96, 128, 64, 32
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(kx, (m, d))
    w = jax.random.normal(kw, (d, f))
    m_tiles, rmax = 3, 2
    r_tile = jnp.asarray([1, 2, 2], jnp.int32)
    probs = amm.block_probs(w, block)
    idx = jax.random.categorical(ks, jnp.log(probs), shape=(m_tiles, rmax))
    inv_rp = 1.0 / (r_tile[:, None] * probs[idx])

    fn = jax.jit(lambda x, w, r, i, p: mca_matmul_ragged(
        x, w, r, i, p, block=block, block_m=128))
    out = fn(x, w, r_tile, idx, inv_rp)
    ref = kref.ref_mca_matmul_ragged(x, w, np.asarray(r_tile), idx, inv_rp,
                                     block, m // m_tiles)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------- clamped block sizes
def test_mca_matmul_clamps_blocks_to_shape():
    """m,f smaller than the requested block sizes must still take the
    kernel path (clamped), not silently mis-dispatch."""
    m, d, f, block, r = 64, 256, 64, 64, 3
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(kx, (m, d))
    w = jax.random.normal(kw, (d, f))
    probs = amm.block_probs(w, block)
    idx, inv_rp = amm.draw_block_samples(ks, probs, r)
    with obs.scoped() as reg:
        out = mca_matmul(x, w, idx, inv_rp, block=block,
                         block_m=128, block_f=128)
        assert reg.counter("kernels.mca_matmul.kernel_calls").value == 1
        assert reg.counter("kernels.mca_matmul.fallback_calls").value == 0
    ref = kref.ref_mca_matmul_fixed(x, w, idx, inv_rp, block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_dispatch_counters_recorded():
    q, k, v = _qkv(1, 2, 2, 64, 64, 32, seed=6)
    scale = 1.0 / np.sqrt(32)
    with obs.scoped() as reg:
        flash_attention(q, k, v, scale=scale, causal=True,
                        block_q=64, block_k=64)
        assert reg.counter(
            "kernels.flash_attention.kernel_calls").value == 1
        # skv=48 not divisible by the clamped bk: must fall back and say so
        flash_attention(q, k[:, :, :48], v[:, :, :48], scale=scale,
                        causal=False, block_q=64, block_k=32)
        assert reg.counter(
            "kernels.flash_attention.fallback_calls").value == 1


# ------------------------------------- prefill attention: kernel or scan
def _gqa128(**over):
    """A tiny GQA decoder whose heads are whole lanes (d_head 128)."""
    from repro.configs import get_config
    from repro.models import build_model, reduced
    cfg = reduced(get_config("starcoder2-3b"), n_layers=2, d_head=128,
                  vocab_size=128, **over)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _flash_calls(fn):
    """Flash kernel call sites a fresh trace of ``fn`` dispatches."""
    with obs.scoped() as reg:
        jax.block_until_ready(fn())
        return reg.counter("kernels.flash_attention.kernel_calls").value


def _tokens(cfg, b, s, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 1,
                              cfg.vocab_size)


@pytest.mark.parametrize("s,on_kernel", [(64, False), (128, True),
                                         (256, True), (192, False)])
def test_prefill_dispatch_by_bucket(s, on_kernel):
    """A cache-filling prefill runs attention on the flash kernel where
    the bucket tiles (128 or more, whole tiles), else on the scan."""
    cfg, model, params = _gqa128()
    batch = {"tokens": _tokens(cfg, 1, s),
             "pos_offset": jnp.asarray([s // 4], jnp.int32)}
    calls = _flash_calls(lambda: jax.jit(
        lambda p: model.prefill(p, batch, s + 8)[1])(params))
    assert (calls > 0) == on_kernel


@pytest.mark.parametrize("case,on_kernel", [
    ("mca_on", False), ("mca_configured_exact", True),
    ("window_narrower", False), ("window_wider", True)])
def test_prefill_dispatch_documented_paths(case, on_kernel):
    """MCA on V/O keeps the scan (it needs the softmax statistics); MCA
    configured but run exact (no key) takes the kernel; a window narrower
    than the sequence keeps the scan, one as wide does not matter."""
    from repro.core.policy import MCAConfig
    s = 128
    over = {"mca_on": {"mca": MCAConfig(enabled=True, alpha=0.3, block=16,
                                        sites=("v_proj", "o_proj"))},
            "window_narrower": {"window": 64},
            "window_wider": {"window": 256}}.get(case, {})
    if case == "mca_configured_exact":
        over = over or {"mca": MCAConfig(enabled=True, alpha=0.3, block=16,
                                         sites=("v_proj", "o_proj"))}
    cfg, model, params = _gqa128(**over)
    key = jax.random.PRNGKey(1) if case == "mca_on" else None
    batch = {"tokens": _tokens(cfg, 1, s)}
    calls = _flash_calls(lambda: jax.jit(
        lambda p: model.prefill(p, batch, s + 8, key)[1])(params))
    assert (calls > 0) == on_kernel


def test_training_dispatches_no_flash_call():
    """The training loss and its gradient never take the forward-only
    kernel, even at a sequence that tiles."""
    cfg, model, params = _gqa128()
    toks = _tokens(cfg, 2, 128)
    batch = {"tokens": toks, "labels": toks}
    grad = jax.jit(jax.grad(lambda p: model.loss(p, batch, None)[0]))
    with obs.scoped() as reg:
        jax.block_until_ready(grad(params))
        assert reg.counter("kernels.flash_attention.kernel_calls").value == 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_kernel_logits_match_scan(dtype, monkeypatch):
    """Prefill logits and the cached K/V of real positions on the kernel
    path match the scan's (``onepass_attention``) for a left-padded batch
    (pad positions hold K/V that every read masks out)."""
    from repro.models import attention
    from repro.models.api import _logits
    cfg, model, params = _gqa128(dtype=dtype)
    s = 256
    batch = {"tokens": _tokens(cfg, 2, s, seed=2),
             "pos_offset": jnp.asarray([0, 77], jnp.int32)}

    def run():
        cache, hid, _ = jax.jit(
            lambda p: model.prefill(p, batch, s + 8))(params)
        kv = cache["layers"]
        real = [kv[n][:, r, off:s] for n in ("k", "v")
                for r, off in enumerate((0, 77))]
        return _logits(params, cfg, hid[:, -1:]), real

    assert _flash_calls(lambda: run()[0]) > 0
    got, got_cache = run()
    monkeypatch.setattr(attention, "_use_flash_prefill",
                        lambda *a, **k: False)
    assert _flash_calls(lambda: run()[0]) == 0
    want, want_cache = run()
    tol = 1e-4 if dtype == "float32" else 2 * float(
        jnp.finfo(jnp.bfloat16).eps)
    for a, b in zip(jax.tree.leaves((got, got_cache)),
                    jax.tree.leaves((want, want_cache))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b)


def test_generate_left_padded_batch_on_kernel_matches_solo():
    """``Engine.generate``'s batched left-padded prefill takes the kernel,
    one offset per row, and each row generates what it would alone (on
    the scan: 100 positions do not tile)."""
    from repro.serve import Engine
    cfg, model, params = _gqa128()
    eng = Engine(model, params, batch_size=2, max_len=160)
    rng = np.random.default_rng(0)
    a = rng.integers(1, cfg.vocab_size, 128).astype(np.int32)
    b = rng.integers(1, cfg.vocab_size, 100).astype(np.int32)
    padded = np.stack([a, np.pad(b, (28, 0))])
    with obs.scoped() as reg:
        out = eng.generate(padded, max_new=4,
                           prompt_lens=np.asarray([128, 100]))
        assert reg.counter("kernels.flash_attention.kernel_calls").value > 0
    with obs.scoped() as reg:
        solo_b = eng.generate(np.stack([b, b]), max_new=4)[0]
        assert reg.counter("kernels.flash_attention.kernel_calls").value == 0
    np.testing.assert_array_equal(out[1], solo_b)
