"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ref_mca_matmul_fixed(x, w, idx, inv_rp, block=128):
    """Oracle for mca_matmul_fixed: weighted sum of sampled block products."""
    m, d = x.shape
    _, f = w.shape
    k = d // block
    xb = x.reshape(m, k, block)
    wb = w.reshape(k, block, f)
    xg = jnp.take(xb, idx, axis=1)                 # [m, R, B]
    wg = jnp.take(wb, idx, axis=0)                 # [R, B, f]
    out = jnp.einsum("mrb,rbf,r->mf", xg.astype(jnp.float32),
                     wg.astype(jnp.float32), inv_rp.astype(jnp.float32))
    return out.astype(x.dtype)


def ref_mca_matmul_ragged(x, w, r_tile, idx, inv_rp, block=128, block_m=128):
    """Oracle for mca_matmul_ragged: per-row-tile prefix of the sample list."""
    m, d = x.shape
    _, f = w.shape
    bm = min(block_m, m)
    outs = []
    for t in range(m // bm):
        r = int(r_tile[t])
        outs.append(ref_mca_matmul_fixed(
            x[t * bm:(t + 1) * bm], w, idx[t, :r], inv_rp[t, :r], block))
    return jnp.concatenate(outs, axis=0)


def ref_attention(q, k, v, *, scale, causal=True):
    """Materialized-A attention. q:[B,Hq,Sq,dh] k,v:[B,Hkv,Skv,dh].

    Returns (out [B,Hq,Sq,dh], lse [B,Hq,Sq] f32).
    """
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        s = jnp.where(mask[None, None], s, NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    a = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", a, vr.astype(jnp.float32))
    return out.astype(q.dtype), lse


def ref_colmax(q, k, lse, *, scale, causal=True):
    """Oracle for attn_colmax: max_i exp(s_ij - lse_i), per query head."""
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    kr = jnp.repeat(k, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    a = jnp.exp(s - lse[..., None])
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        a = jnp.where(mask[None, None], a, 0.0)
    return jnp.max(a, axis=2)        # over queries -> [B,Hq,Skv]


def ref_decode_attention(q, k, v, valid, s1, v1, layer, *, scale, hkv,
                         chunk):
    """Oracle (and fallback) for decode_attention: the same online softmax
    over ``chunk``-row slices of layer ``layer`` of the stack, read with
    dynamic slices.  Shapes and returns as ``decode_attention``."""
    b, r, dh = q.shape
    n = k.shape[2]
    g = r // hkv
    own = (jnp.arange(r)[:, None] // g) == (jnp.arange(n)[None] % hkv)

    def step(carry, ci):
        m, l, acc = carry
        start = (layer, 0, ci * chunk, 0)
        kc = jax.lax.dynamic_slice(k, start, (1, b, chunk, dh))[0]
        vc = jax.lax.dynamic_slice(v, start, (1, b, chunk, dh))[0]
        ok = (jax.lax.dynamic_slice_in_dim(own, ci * chunk, chunk, axis=1)
              & (jax.lax.dynamic_slice_in_dim(valid, ci * chunk, chunk,
                                              axis=2) != 0))
        s = jnp.einsum("brd,bnd->brn", q, kc,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("brn,bnd->brd", p.astype(vc.dtype), vc,
                                      preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    init = (s1.astype(jnp.float32), jnp.ones_like(s1, jnp.float32),
            v1.astype(jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, jnp.arange(n // chunk))
    return (acc / l).astype(q.dtype), l
