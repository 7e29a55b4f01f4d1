"""Kernel ↔ reference parity (Pallas interpret mode on CPU).

Complements test_kernels.py's randomized allclose checks with the two
contractual properties the MCA pipeline relies on:

  exact mode    enumerating every block once with unit weights makes
                mca_matmul IDENTICAL to the dense product (and to
                kernels/ref.py), so the "exact tier" of the tiered
                dispatch is a true fallback, not an approximation;
  sampled mode  the kernel's Monte-Carlo error obeys the paper's Lemma-1
                bound E||err_row|| <= ||X[j]|| ||W||_F / sqrt(r).

On CPU the wrappers in kernels/ops.py run every Pallas body with
interpret=True; shapes here are chosen so the kernel path (not the jnp
fallback) is exercised: m % block_m == 0, d % block == 0, block >= 128.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import amm, error_bounds
from repro.kernels import attn_colmax, flash_attention, mca_matmul
from repro.kernels import ref as kref

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------- exact mode
@pytest.mark.parametrize("m,d,f,block", [
    (128, 512, 128, 128),
    (256, 256, 256, 128),
])
def test_mca_matmul_exact_mode_equals_dense(m, d, f, block):
    """idx = (0..K-1), inv_rp = 1: the estimator degenerates to the exact
    blocked matmul — must match X @ W to f32 accumulation precision."""
    kx, kw = jax.random.split(jax.random.PRNGKey(m + d), 2)
    x = jax.random.normal(kx, (m, d))
    w = jax.random.normal(kw, (d, f))
    k = d // block
    idx = jnp.arange(k, dtype=jnp.int32)
    inv_rp = jnp.ones((k,), jnp.float32)
    out = mca_matmul(x, w, idx, inv_rp, block=block)
    dense = jnp.dot(x, w, preferred_element_type=jnp.float32)
    # vs dense: accumulation ORDER differs (per-block partial sums), so this
    # is fp-tolerance equality, not bitwise
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)
    ref = kref.ref_mca_matmul_fixed(x, w, idx, inv_rp, block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_exact_equals_reference():
    """The flash kernel is exact (reordered, not approximated): out and lse
    must match the materialized-A oracle tightly in f32."""
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    b, h, s, dh = 2, 4, 128, 64
    q = jax.random.normal(kq, (b, h, s, dh))
    k = jax.random.normal(kk, (b, h, s, dh))
    v = jax.random.normal(kv, (b, h, s, dh))
    scale = dh ** -0.5
    for causal in (False, True):
        out, lse = flash_attention(q, k, v, scale=scale, causal=causal,
                                   block_q=64, block_k=64)
        ref_out, ref_lse = kref.ref_attention(q, k, v, scale=scale,
                                              causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=1e-5, atol=1e-5)


def test_attn_colmax_exact_equals_reference():
    key = jax.random.PRNGKey(1)
    kq, kk = jax.random.split(key)
    b, h, s, dh = 1, 2, 128, 64
    q = jax.random.normal(kq, (b, h, s, dh))
    k = jax.random.normal(kk, (b, h, s, dh))
    scale = dh ** -0.5
    for causal in (False, True):
        _, lse = flash_attention(q, k, jnp.zeros_like(k), scale=scale,
                                 causal=causal, block_q=64, block_k=64)
        cm = attn_colmax(q, k, lse, scale=scale, causal=causal,
                         block_q=64, block_k=64, reduce_heads=False)
        ref = kref.ref_colmax(q, k, lse, scale=scale, causal=causal)
        np.testing.assert_allclose(np.asarray(cm), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- sampled mode
@pytest.mark.parametrize("r", [2, 4, 8])
def test_mca_matmul_sampled_error_within_lemma1_bound(r):
    """Empirical E||err_row|| from the KERNEL path stays under the paper's
    Lemma-1 bound (Eq. 7).  64 fixed-seed trials estimate the expectation;
    25% slack covers MC noise on the mean (same margin as test_core_policy)."""
    m, d, f, block = 128, 512, 128, 128
    kx, kw = jax.random.split(jax.random.PRNGKey(42), 2)
    x = jax.random.normal(kx, (m, d))
    w = jax.random.normal(kw, (d, f))
    probs = amm.block_probs(w, block)
    exact = jnp.dot(x, w, preferred_element_type=jnp.float32)

    @jax.jit
    def one(key):
        idx, inv_rp = amm.draw_block_samples(key, probs, r)
        est = mca_matmul(x, w, idx, inv_rp, block=block)
        return jnp.linalg.norm(est - exact, axis=-1)         # [m]

    keys = jax.random.split(jax.random.PRNGKey(7), 64)
    errs = jnp.stack([one(k) for k in keys])                 # [T, m]
    mean_err = jnp.mean(errs, axis=0)                        # per-row E||err||
    bound = error_bounds.lemma1_bound(
        jnp.linalg.norm(x, axis=-1), error_bounds.w_fro(w),
        jnp.full((m,), r, jnp.float32))
    assert bool(jnp.all(mean_err <= 1.25 * bound)), (
        float(jnp.max(mean_err / bound)))


def test_sampled_error_shrinks_with_r():
    """Doubling r must not increase the empirical error (1/sqrt(r) decay)."""
    m, d, f, block = 128, 512, 128, 128
    kx, kw = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(kx, (m, d))
    w = jax.random.normal(kw, (d, f))
    probs = amm.block_probs(w, block)
    exact = jnp.dot(x, w, preferred_element_type=jnp.float32)

    def mean_err(r):
        @jax.jit
        def one(k):
            return jnp.linalg.norm(
                mca_matmul(x, w, *amm.draw_block_samples(k, probs, r),
                           block=block) - exact)
        keys = jax.random.split(jax.random.PRNGKey(11), 32)
        return float(jnp.mean(jnp.stack([one(k) for k in keys])))

    e1, e2, e4 = mean_err(1), mean_err(2), mean_err(4)
    assert e2 < e1 and e4 < e2, (e1, e2, e4)


# ------------------------------------------------------ causal prefill
def _prefill_tiles(s, bq, bk, offs):
    """Score tiles of one kv head's query-head group the padded causal
    kernel must compute: below the diagonal, past each row's padding."""
    return sum(1 for off in offs for i in range(s // bq)
               for j in range(s // bk)
               if j * bk <= i * bq + bq - 1 and j * bk + bk > off
               and i * bq + bq > off)


@pytest.mark.parametrize("s,bq,bk", [(128, 128, 128), (256, 128, 128),
                                     (512, 256, 128), (512, 128, 256)])
@pytest.mark.parametrize("g", [1, 3])
@pytest.mark.parametrize("pad", ["none", "in_tile", "whole_tiles"])
def test_flash_prefill_matches_onepass(s, bq, bk, g, pad):
    """The padded causal GQA prefill kernel against the scan passes with
    ``kv_valid``, bf16 inputs: real rows agree within bf16 round-off, pad
    rows come out finite (zero), and the kernel computes exactly the tiles
    left after causal and padding skips."""
    from repro.kernels.flash_attention import flash_prefill
    from repro.kernels.telemetry import LANE_COUNT
    from repro.models.attention import onepass_attention
    hkv, dh, b = 2, 128, 2
    first = {"none": 0, "in_tile": 37, "whole_tiles": s // 2 + 5}[pad]
    offs = jnp.asarray([first, first // 3], jnp.int32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(s + g), 3)
    bf = jnp.bfloat16
    q = jax.random.normal(kq, (b, s, hkv, g, dh), bf)
    k = jax.random.normal(kk, (b, s, hkv, dh), bf)
    v = jax.random.normal(kv, (b, s, hkv, dh), bf)
    scale = dh ** -0.5
    out, tel = flash_prefill(
        q.reshape(b, s, -1), k.reshape(b, s, -1), v.reshape(b, s, -1), offs,
        n_kv_heads=hkv, scale=scale, block_q=bq, block_k=bk, interpret=True,
        telemetry=True)
    valid = jnp.arange(s)[None] >= offs[:, None]
    ref, _, _ = onepass_attention(q, k, v, scale=scale, causal=True,
                                  window=0, chunk=64, kv_valid=valid)
    got = np.asarray(out, np.float32).reshape(b, s, -1)
    want = np.asarray(ref, np.float32).reshape(b, s, -1)
    real = np.asarray(valid)
    np.testing.assert_allclose(got[real], want[real], rtol=2e-2, atol=2e-2)
    assert np.all(got[~real] == 0.0)
    assert int(tel[0, LANE_COUNT]) == hkv * g * _prefill_tiles(
        s, bq, bk, [int(o) for o in offs])
