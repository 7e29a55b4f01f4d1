"""Attention: memory-efficient chunked softmax attention (pure JAX, XLA-
lowerable on any backend) with the MCA hooks, plus GQA / MLA modules and
KV-cache decode paths.

Layout convention: activations are [B, S, H, dh] (seq-major); GQA never
materializes repeated KV (einsum over grouped heads).

The chunked two-pass structure mirrors kernels/flash_attention.py +
kernels/attn_colmax.py.  A cache-filling GQA prefill with MCA off runs on
the flash kernel where its shapes tile (``_use_flash_prefill``, on every
backend: interpret mode on the CPU); everything else, training included,
runs the lax.scan passes.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import MCAConfig, mca_project
from repro.dist.context import (DP, constrain, constrain_heads,
                                get_mesh)
from repro.kernels import ops as kernel_ops
from .common import apply_rope, dense_init, maybe_scan, rmsnorm

NEG_INF = -1e30


def pick_chunk(s: int, target: int) -> int:
    """Largest divisor of s that is <= target."""
    c = min(target, s)
    while s % c != 0:
        c -= 1
    return c


def _mask(qpos, kpos, causal: bool, window: int):
    """qpos: [Sq], kpos: [C] -> bool [Sq, C] (True = attend)."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window > 0:
        m &= qpos[:, None] - kpos[None, :] < window
    return m


def _scores(q, k_chunk, scale):
    """q: [B,Sq,Hkv,G,dh]; k_chunk: [B,C,Hkv,dh] -> [B,Hkv,G,Sq,C] f32."""
    return jnp.einsum("bqhgd,bchd->bhgqc", q, k_chunk,
                      preferred_element_type=jnp.float32) * scale


def _kv_chunks(x, chunk):
    b, s, h, d = x.shape
    return jnp.moveaxis(x.reshape(b, s // chunk, chunk, h, d), 1, 0)


# --------------------------------------------------------- chunked passes
def chunked_lse(q, k, *, scale, causal, window, chunk, q_offset=0,
                unroll=False, kv_valid=None):
    """Pass 1: per-query (m, lse). q: [B,Sq,Hkv,G,dh]; k: [B,Skv,Hkv,dh].

    kv_valid: optional [B, Skv] bool — False marks left-padding keys that
    must contribute nothing (score forced to NEG_INF).
    Returns (m, lse), each [B,Hkv,G,Sq] float32.
    """
    b, sq, hkv, g, dh = q.shape
    skv = k.shape[1]
    qpos = q_offset + jnp.arange(sq)
    kcs = _kv_chunks(k, chunk)

    def step(carry, inp):
        m, l = carry
        ci, kc = inp
        s = _scores(q, kc, scale)
        kpos = ci * chunk + jnp.arange(chunk)
        s = jnp.where(_mask(qpos, kpos, causal, window)[None, None, None],
                      s, NEG_INF)
        if kv_valid is not None:
            kvc = jax.lax.dynamic_slice_in_dim(kv_valid, ci * chunk, chunk,
                                               axis=1)
            s = jnp.where(kvc[:, None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(s - m_new[..., None]),
                                             axis=-1)
        return (m_new, l), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    (m, l), _ = maybe_scan(jax.checkpoint(step), (m0, l0),
                           (jnp.arange(skv // chunk), kcs), unroll)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return m, m + jnp.log(safe_l)


def chunked_colmax(q, k, lse, *, scale, causal, window, chunk,
                   q_offset=0, unroll=False, kv_valid=None, q_valid=None):
    """max_i A[i, j] given lse — the Eq. 9 driver. Returns [B, Skv] f32.

    kv_valid ([B, Skv]) zeroes padding key columns; q_valid ([B, Sq])
    excludes padding query rows (their lse is garbage) from the max.
    """
    b, sq, hkv, g, dh = q.shape
    skv = k.shape[1]
    qpos = q_offset + jnp.arange(sq)
    kcs = _kv_chunks(k, chunk)

    def step(_, inp):
        ci, kc = inp
        s = _scores(q, kc, scale)
        a = jnp.exp(s - lse[..., None])
        kpos = ci * chunk + jnp.arange(chunk)
        a = jnp.where(_mask(qpos, kpos, causal, window)[None, None, None],
                      a, 0.0)
        if kv_valid is not None:
            kvc = jax.lax.dynamic_slice_in_dim(kv_valid, ci * chunk, chunk,
                                               axis=1)
            a = jnp.where(kvc[:, None, None, None, :], a, 0.0)
        if q_valid is not None:
            a = jnp.where(q_valid[:, None, None, :, None], a, 0.0)
        return None, jnp.max(a, axis=(1, 2, 3))        # -> [B, C]

    _, cms = maybe_scan(jax.checkpoint(step), None,
                        (jnp.arange(skv // chunk), kcs), unroll)
    return jnp.moveaxis(cms, 0, 1).reshape(b, skv)


def chunked_av(q, k, v, lse, *, scale, causal, window, chunk,
               q_offset=0, unroll=False, kv_valid=None):
    """Pass 2: O = A @ V given lse. Returns [B,Sq,Hkv,G,dv] in v.dtype.
    (dv may differ from the q/k head dim, e.g. MLA.)"""
    b, sq, hkv, g, _ = q.shape
    dv = v.shape[-1]
    skv = k.shape[1]
    qpos = q_offset + jnp.arange(sq)
    kcs = _kv_chunks(k, chunk)
    vcs = _kv_chunks(v, chunk)

    def step(acc, inp):
        ci, kc, vc = inp
        s = _scores(q, kc, scale)
        a = jnp.exp(s - lse[..., None])
        kpos = ci * chunk + jnp.arange(chunk)
        a = jnp.where(_mask(qpos, kpos, causal, window)[None, None, None],
                      a, 0.0)
        if kv_valid is not None:
            kvc = jax.lax.dynamic_slice_in_dim(kv_valid, ci * chunk, chunk,
                                               axis=1)
            a = jnp.where(kvc[:, None, None, None, :], a, 0.0)
        acc += jnp.einsum("bhgqc,bchd->bqhgd", a.astype(v.dtype), vc,
                          preferred_element_type=jnp.float32)
        return acc, None

    acc0 = jnp.zeros((b, sq, hkv, g, dv), jnp.float32)
    acc, _ = maybe_scan(jax.checkpoint(step), acc0,
                        (jnp.arange(skv // chunk), kcs, vcs), unroll)
    return acc.astype(v.dtype)


def onepass_attention(q, k, v, *, scale, causal, window, chunk,
                      q_offset=0, unroll=False, kv_valid=None):
    """Single-pass online-softmax attention (no colmax). Returns
    (out [B,Sq,Hkv,G,dv], m, lse)."""
    b, sq, hkv, g, _ = q.shape
    dv = v.shape[-1]
    skv = k.shape[1]
    qpos = q_offset + jnp.arange(sq)
    kcs = _kv_chunks(k, chunk)
    vcs = _kv_chunks(v, chunk)

    def step(carry, inp):
        m, l, acc = carry
        ci, kc, vc = inp
        s = _scores(q, kc, scale)
        kpos = ci * chunk + jnp.arange(chunk)
        s = jnp.where(_mask(qpos, kpos, causal, window)[None, None, None],
                      s, NEG_INF)
        if kv_valid is not None:
            kvc = jax.lax.dynamic_slice_in_dim(kv_valid, ci * chunk, chunk,
                                               axis=1)
            s = jnp.where(kvc[:, None, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        # correction broadcast to [B,Sq,Hkv,G,1]
        corr_b = jnp.moveaxis(corr, -1, 1)[..., None]
        acc = acc * corr_b + jnp.einsum(
            "bhgqc,bchd->bqhgd", p.astype(v.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, hkv, g, dv), jnp.float32)
    (m, l, acc), _ = maybe_scan(jax.checkpoint(step), (m0, l0, acc0),
                                (jnp.arange(skv // chunk), kcs, vcs), unroll)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = acc / jnp.moveaxis(safe_l, -1, 1)[..., None]
    return out.astype(v.dtype), m, m + jnp.log(safe_l)


def chunked_lse_colmax_fused(q, k, *, scale, causal, window, chunk,
                             q_offset=0, unroll=False, kv_valid=None,
                             q_valid=None):
    """One-pass lse + CONSERVATIVE colmax (beyond-paper optimization).

    True colmax needs the final lse (a second O(S^2) sweep). Folding
    max_i exp(s_ij - lse_running_i) during pass 1 uses a *partial* lse
    (<= final), so the result OVERestimates every column max: Eq.9 then
    allocates at least as many samples as the exact schedule and the
    Theorem-2 bound is preserved — at zero extra score sweeps.

    Returns (m, lse, colmax_over [B,Skv])."""
    b, sq, hkv, g, dh = q.shape
    skv = k.shape[1]
    qpos = q_offset + jnp.arange(sq)
    kcs = _kv_chunks(k, chunk)

    def step(carry, inp):
        m, l = carry
        ci, kc = inp
        s = _scores(q, kc, scale)
        kpos = ci * chunk + jnp.arange(chunk)
        mask = _mask(qpos, kpos, causal, window)[None, None, None]
        if kv_valid is not None:
            kvc = jax.lax.dynamic_slice_in_dim(kv_valid, ci * chunk, chunk,
                                               axis=1)
            mask = mask & kvc[:, None, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(s - m_new[..., None]),
                                             axis=-1)
        lse_run = m_new + jnp.log(jnp.where(l == 0, 1.0, l))
        a_over = jnp.exp(s - lse_run[..., None])
        a_over = jnp.where(mask, a_over, 0.0)
        if q_valid is not None:
            a_over = jnp.where(q_valid[:, None, None, :, None], a_over, 0.0)
        cm = jnp.max(a_over, axis=(1, 2, 3))           # [B, C]
        return (m_new, l), cm

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    (m, l), cms = maybe_scan(jax.checkpoint(step), (m0, l0),
                             (jnp.arange(skv // chunk), kcs), unroll)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    colmax = jnp.minimum(jnp.moveaxis(cms, 0, 1).reshape(b, skv), 1.0)
    return m, m + jnp.log(safe_l), colmax


# ------------------------------------------------------- banded (local)
def _band_starts(sq, window, cq):
    band = window + cq
    idx = jnp.arange(sq // cq)
    return jnp.clip((idx + 1) * cq - band, 0, None), band


def banded_lse_colmax(q, k, *, scale, window, chunk_q, unroll=False):
    """Local-attention pass over gathered KV bands: each query chunk of
    size Cq attends only its [qpos-W, qpos] band (length W+Cq), so no
    out-of-window score is ever computed — O(S*(W+Cq)) instead of O(S^2).

    Because the band covers every key a query may attend, lse is final in
    ONE pass and colmax comes for free (exp(s - lse) folded per band with
    a scatter-max over key positions).

    Returns (m, lse [B,Hkv,G,Sq], colmax [B,Skv])."""
    b, sq, hkv, g, dh = q.shape
    starts, band = _band_starts(sq, window, chunk_q)
    nc = sq // chunk_q

    def step(_, inp):
        i, start = inp
        qs = jax.lax.dynamic_slice_in_dim(q, i * chunk_q, chunk_q, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
        s = _scores(qs, kb, scale)                   # [B,hkv,g,Cq,band]
        qpos = i * chunk_q + jnp.arange(chunk_q)
        kpos = start + jnp.arange(band)
        mask = (qpos[:, None] >= kpos[None, :]) & \
               (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        l = jnp.sum(jnp.exp(s - m[..., None]), axis=-1)
        lse = m + jnp.log(jnp.where(l == 0, 1.0, l))
        a = jnp.exp(s - lse[..., None])
        a = jnp.where(mask[None, None, None], a, 0.0)
        cm_band = jnp.max(a, axis=(1, 2, 3))         # [B, band]
        return None, (m, lse, cm_band)

    _, (ms, lses, cms) = maybe_scan(step, None,
                                    (jnp.arange(nc), starts), unroll)
    m = jnp.moveaxis(ms, 0, -2).reshape(b, hkv, g, sq)
    lse = jnp.moveaxis(lses, 0, -2).reshape(b, hkv, g, sq)
    # scatter-max band colmaxes onto absolute key positions
    kpos = starts[:, None] + jnp.arange(band)[None, :]       # [nc, band]
    colmax = jnp.zeros((b, sq), jnp.float32).at[
        :, kpos.reshape(-1)].max(
        jnp.moveaxis(cms, 0, 1).reshape(b, -1))
    return m, lse, colmax


def banded_av(q, k, v, lse, *, scale, window, chunk_q, unroll=False):
    """O = A @ V over gathered bands given final lse."""
    b, sq, hkv, g, dh = q.shape
    dv = v.shape[-1]
    starts, band = _band_starts(sq, window, chunk_q)
    nc = sq // chunk_q

    def step(_, inp):
        i, start = inp
        qs = jax.lax.dynamic_slice_in_dim(q, i * chunk_q, chunk_q, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
        s = _scores(qs, kb, scale)
        qpos = i * chunk_q + jnp.arange(chunk_q)
        kpos = start + jnp.arange(band)
        mask = (qpos[:, None] >= kpos[None, :]) & \
               (qpos[:, None] - kpos[None, :] < window)
        lse_c = jax.lax.dynamic_slice_in_dim(lse, i * chunk_q, chunk_q,
                                             axis=-1)
        a = jnp.exp(s - lse_c[..., None])
        a = jnp.where(mask[None, None, None], a, 0.0)
        out = jnp.einsum("bhgqc,bchd->bqhgd", a.astype(v.dtype), vb,
                         preferred_element_type=jnp.float32)
        return None, out.astype(v.dtype)

    _, outs = maybe_scan(step, None, (jnp.arange(nc), starts), unroll)
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, hkv, g, dv)


def banded_onepass(q, k, v, *, scale, window, chunk_q, unroll=False):
    """MCA-off local attention: out + (m, lse) over bands (two cheap
    band passes; still ~W/S of the full-scores cost)."""
    m, lse, _ = banded_lse_colmax(q, k, scale=scale, window=window,
                                  chunk_q=chunk_q, unroll=unroll)
    out = banded_av(q, k, v, lse, scale=scale, window=window,
                    chunk_q=chunk_q, unroll=unroll)
    return out, m, lse


def _use_banded(cfg, window, skv, causal, kv_x):
    cq = pick_chunk(skv, cfg.attn_chunk)
    return (cfg.banded_local and window > 0 and causal and kv_x is None
            and skv % cq == 0 and skv >= window + cq)


# ------------------------------------------------------------ GQA module
def init_gqa(key, cfg):
    ks = jax.random.split(key, 4)
    dt = cfg.jnp_dtype
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * cfg.d_head, dt),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim, dt),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim, dt),
        "wo": dense_init(ks[3], cfg.n_heads * cfg.d_head, cfg.d_model, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.d_head,), jnp.float32)
        p["k_norm"] = jnp.zeros((cfg.d_head,), jnp.float32)
    return p


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def _zero_stats(n_tiers: int):
    """Per-layer MCA stats accumulator; tier_hist is padded to the static
    cfg.mca.n_tiers length so it survives lax.scan carries."""
    return {"exact_flops": jnp.zeros((), jnp.float32),
            "mca_flops": jnp.zeros((), jnp.float32),
            "tier_hist": jnp.zeros((n_tiers,), jnp.float32)}


def _acc_stats(acc, s):
    out = {"exact_flops": acc["exact_flops"] + jnp.asarray(
               s["exact_flops"], jnp.float32),
           "mca_flops": acc["mca_flops"] + jnp.asarray(
               s["mca_flops"], jnp.float32),
           "tier_hist": acc["tier_hist"]}
    if "tier_hist" in s:
        # the ladder may be shorter than n_tiers for small d; pad with
        # zeros at the exact end
        h = jnp.asarray(s["tier_hist"], jnp.float32)
        out["tier_hist"] = out["tier_hist"].at[:h.shape[0]].add(h)
    return out


def _use_flash_prefill(cfg, *, return_kv, kv_x, causal, window, mca_on, nm,
                       s):
    """Whether a full-sequence GQA call runs on the flash prefill kernel:
    a causal self-attention that fills a cache (so it is never
    differentiated, and the kernel needs no backward), with MCA off on
    V and O, no window narrower than the sequence, no model-axis
    sharding, and shapes that tile (``kernels.ops.flash_prefill_tiles``).
    Everything else runs the scan passes."""
    return (return_kv and kv_x is None and causal and not mca_on
            and (window == 0 or window >= s) and nm == 1
            and kernel_ops.flash_prefill_tiles(s, cfg.d_head))


@jax.named_scope("attention")
def gqa_attention(p, cfg, x, *, pos, mca_key=None, causal=None,
                  window=None, kv_x=None, return_kv=False, pos_offset=None):
    """Full-sequence (train / prefill) GQA attention with MCA on V/O.

    x: [B, S, d]; kv_x: cross-attention source (defaults to x);
    pos_offset: optional [B] int32 left-padding counts of the
    self-attention sequence — padding keys are masked out of
    scores/colmax and padding query rows out of rowmax.
    Returns (y, kv_or_None, stats, rowmax): rowmax [B, Sq] is the O-site
    importance, None on the flash prefill path (MCA off there).
    """
    causal = cfg.causal if causal is None else causal
    window = cfg.window if window is None else window
    b, sq, d = x.shape
    src = x if kv_x is None else kv_x
    skv = src.shape[1]
    hkv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    dh = cfg.d_head
    scale = dh ** -0.5
    stats = _zero_stats(cfg.mca.n_tiers)
    kv_valid = (None if pos_offset is None
                else jnp.arange(skv)[None] >= pos_offset[:, None])
    # in self-attention, query validity is key validity
    q_valid = kv_valid if kv_x is None else None
    # TP-friendly head grouping: when KV heads can't shard over "model" but
    # the full q-head count can, repeat KV to H heads (g=1) so the single
    # head dim shards cleanly (Megatron GQA-TP; repeat is a local
    # broadcast of replicated KV, not a collective).
    mesh = get_mesh()
    nm = mesh.shape.get("model", 1) if mesh is not None else 1
    shardable = cfg.n_heads % nm == 0 or hkv % nm == 0
    seq_par = nm > 1 and (cfg.attn_parallel in ("seq", "dp") or
                          (cfg.attn_parallel == "auto" and not shardable))
    repeat_kv = (not seq_par and nm > 1 and hkv % nm != 0
                 and cfg.n_heads % nm == 0 and g > 1)

    q = _split_heads(x @ p["wq"], cfg.n_heads, dh)
    k = _split_heads(src @ p["wk"], hkv, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    kv_pos = jnp.arange(skv) if kv_x is not None else pos
    q = apply_rope(q, pos, cfg.rope_theta, cfg.rotary_pct)
    k = apply_rope(k, kv_pos, cfg.rope_theta, cfg.rotary_pct)
    k_cache = k
    mca_v = cfg.mca.active("v_proj") and mca_key is not None
    mca_o = cfg.mca.active("o_proj") and mca_key is not None
    if _use_flash_prefill(cfg, return_kv=return_kv, kv_x=kv_x,
                          causal=causal, window=window,
                          mca_on=mca_v or mca_o, nm=nm, s=skv):
        v_cache = _split_heads(src @ p["wv"], hkv, dh)
        flat = lambda a: a.reshape(b, skv, -1)              # noqa: E731
        out = kernel_ops.flash_prefill(flat(q), flat(k), flat(v_cache),
                                       pos_offset, scale=scale,
                                       n_kv_heads=hkv)
        return out @ p["wo"], (k_cache, v_cache), stats, None
    if repeat_kv:
        k = jnp.repeat(k, g, axis=2)
        hkv_eff, g_eff = cfg.n_heads, 1
    else:
        hkv_eff, g_eff = hkv, g
    if seq_par:
        # sequence-parallel attention: queries stay seq-sharded, KV is
        # gathered (replicated) — scores/softmax/AV are shard-local.
        # Indivisible seq (whisper's 1500 frames): batch over all axes.
        qg = q.reshape(b, sq, hkv_eff, g_eff, dh)
        if sq % nm == 0 and cfg.attn_parallel != "dp":
            k = constrain(k, DP, None, None, None)
            qg = constrain(qg, DP, "model", None, None, None)
        else:
            from repro.dist.context import DPM
            k = constrain(k, DPM, None, None, None)
            qg = constrain(qg, DPM, None, None, None, None)
    else:
        k = constrain_heads(k, head_dims=(2,))
        qg = q.reshape(b, sq, hkv_eff, g_eff, dh)
        qg = constrain_heads(qg, head_dims=(2, 3))

    chunk = pick_chunk(skv, cfg.attn_chunk)
    # the banded gather path has no padding-mask support; fall back to the
    # chunked passes for ragged (left-padded) batches
    banded = _use_banded(cfg, window, skv, causal, kv_x) and kv_valid is None

    if mca_v:
        if banded:
            m, lse, colmax = banded_lse_colmax(
                qg, k, scale=scale, window=window, chunk_q=chunk,
                unroll=cfg.unroll_inner)
        elif cfg.mca.fast_colmax:
            m, lse, colmax = chunked_lse_colmax_fused(
                qg, k, scale=scale, causal=causal, window=window,
                chunk=chunk, unroll=cfg.unroll_inner, kv_valid=kv_valid,
                q_valid=q_valid)
        else:
            m, lse = chunked_lse(qg, k, scale=scale, causal=causal,
                                 window=window, chunk=chunk,
                                 unroll=cfg.unroll_inner, kv_valid=kv_valid)
            colmax = chunked_colmax(qg, k, lse, scale=scale, causal=causal,
                                    window=window, chunk=chunk,
                                    unroll=cfg.unroll_inner,
                                    kv_valid=kv_valid, q_valid=q_valid)
        with jax.named_scope("mca"):
            kv, s_v = mca_project(jax.random.fold_in(mca_key, 1), src,
                                  p["wv"], colmax, skv, cfg.mca, "v_proj")
        stats = _acc_stats(stats, s_v)
        v_cache = _split_heads(kv, hkv, dh)
        v = jnp.repeat(v_cache, g, axis=2) if repeat_kv else v_cache
        if banded:
            out = banded_av(qg, k, v, lse, scale=scale, window=window,
                            chunk_q=chunk, unroll=cfg.unroll_inner)
        else:
            out = chunked_av(qg, k, v, lse, scale=scale, causal=causal,
                             window=window, chunk=chunk,
                             unroll=cfg.unroll_inner, kv_valid=kv_valid)
        rowmax = jnp.exp(jnp.max(m - lse, axis=(1, 2)))     # [B, Sq]
    else:
        v_cache = _split_heads(src @ p["wv"], hkv, dh)
        v = jnp.repeat(v_cache, g, axis=2) if repeat_kv else v_cache
        if banded:
            out, m, lse = banded_onepass(qg, k, v, scale=scale,
                                         window=window, chunk_q=chunk,
                                         unroll=cfg.unroll_inner)
        else:
            out, m, lse = onepass_attention(
                qg, k, v, scale=scale, causal=causal, window=window,
                chunk=chunk, unroll=cfg.unroll_inner, kv_valid=kv_valid)
        rowmax = jnp.exp(jnp.max(m - lse, axis=(1, 2)))
    if q_valid is not None:
        # padding query rows carry garbage lse; zero importance keeps them
        # in the cheapest tier and out of capacity competition
        rowmax = jnp.where(q_valid, rowmax, 0.0)

    out = out.reshape(b, sq, cfg.n_heads * dh)
    if mca_o:
        with jax.named_scope("mca"):
            y, s_o = mca_project(jax.random.fold_in(mca_key, 2), out,
                                 p["wo"], rowmax, sq, cfg.mca, "o_proj")
        stats = _acc_stats(stats, s_o)
    else:
        y = out @ p["wo"]

    # cache holds the (possibly MCA-encoded) V at the ORIGINAL kv-head
    # count — decode reuses H-tilde, matching Y = A @ H-tilde semantics.
    kv_out = (k_cache, v_cache) if return_kv else None
    return y, kv_out, stats, rowmax


# ------------------------------------------------------------ GQA decode
def init_gqa_cache(cfg, batch, max_len, dtype):
    w = cfg.window if cfg.window > 0 else 0
    slots = w if w else max_len
    return {
        "k": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.d_head), dtype),
        "v": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.d_head), dtype),
        "slot_pos": jnp.full((batch, slots), -1, jnp.int32),
    }


@jax.named_scope("attention")
def gqa_decode(p, cfg, x, cache, *, t, layer, visible, pos_off=None):
    """Single-token decode of layer ``layer`` of a layer-stacked cache.

    x: [B, 1, d]; cache: {"k", "v": [L, B, slots, hkv, dh], "slot_pos":
    [L, B, slots]}, the whole stack, read and not written here; layer:
    scalar int32; t: scalar or [B] int32 position; visible: [B, slots]
    bool, the slots the step may attend (``gqa_visible``, the same for
    every layer).

    The query attends to the layer's cache as it was before this step
    plus the current token's own k/v, joined in the softmax
    (``kernels.decode_attention``, which reads the layer's K/V straight
    from the stack).  The new K/V rows are returned, and ``gqa_write``
    puts every layer's rows into the stack in place once the step's layer
    scan is done.  The slot they will take is masked out of the old
    cache, so the attended positions are those of a write-first decode.

    A scalar ``t`` is the classic lockstep decode (one shared position); a
    per-row ``t`` vector is the per-slot continuous-batching path, where
    every batch row advances at its own sequence position and K/V land at
    per-row cache slots.

    pos_off: optional [B] int32 left-padding offsets — slots whose global
    position predates a batch row's first real token are masked for that
    row, and RoPE positions shift to t - pos_off[b].
    Returns (y, rows {"k", "v": [B, 1, hkv, dh]}, rowmax [B,1])."""
    b = x.shape[0]
    hkv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    dh = cfg.d_head
    scale = dh ** -0.5
    slots = cache["k"].shape[2]
    dt = cache["k"].dtype
    off = jnp.zeros((b,), jnp.int32) if pos_off is None else pos_off
    t_vec = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))

    q = _split_heads(x @ p["wq"], cfg.n_heads, dh)
    k1 = _split_heads(x @ p["wk"], hkv, dh)
    v1 = _split_heads(x @ p["wv"], hkv, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k1 = rmsnorm(k1, p["k_norm"], cfg.norm_eps)
    posb = t_vec[:, None] - off[:, None]
    q = apply_rope(q, posb, cfg.rope_theta, cfg.rotary_pct)
    k1 = apply_rope(k1, posb, cfg.rope_theta, cfg.rotary_pct).astype(dt)
    v1 = v1.astype(dt)

    qh = q.reshape(b, hkv, g, dh)
    s1 = jnp.einsum("bhgd,bhd->bhg", qh, k1[:, 0],
                    preferred_element_type=jnp.float32) * scale
    s1 = jnp.where((t_vec >= off)[:, None, None], s1, NEG_INF)
    v1g = jnp.broadcast_to(v1[:, 0, :, None], (b, hkv, g, dh))
    n_layers = cache["k"].shape[0]
    fold = lambda c: c.reshape(n_layers, b, slots * hkv, dh)  # noqa: E731
    out, l = kernel_ops.decode_attention(
        qh.reshape(b, hkv * g, dh), fold(cache["k"]), fold(cache["v"]),
        jnp.repeat(visible, hkv, axis=1)[:, None],
        s1.reshape(b, hkv * g, 1), v1g.reshape(b, hkv * g, dh), layer,
        scale=scale, hkv=hkv)
    rowmax = jnp.max(1.0 / l, axis=(1, 2))[:, None]            # [B, 1]
    out = out.reshape(b, 1, cfg.n_heads * dh)
    y = out @ p["wo"]
    return y, {"k": k1, "v": v1}, rowmax


def gqa_visible(cfg, cache, t, pos_off=None):
    """[B, slots] bool: the cached slots a decode step at position ``t``
    may attend, in every layer of the stacked GQA cache.

    Every layer holds the same ``slot_pos`` (prefill, slot insertion and
    ``gqa_write`` write them alike), so one layer's decides for all.  They
    are per-row global (pre-offset) positions, so the rolling-window
    wraparound composes with the per-row padding mask ``pos_off``; the
    slot this step's token will take is left out, since the token joins
    the softmax itself."""
    spos = cache["slot_pos"][0]
    b, slots = spos.shape
    t_vec = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
    off = jnp.zeros((b,), jnp.int32) if pos_off is None else pos_off
    slot = _decode_slot(cfg, t_vec, slots)
    return ((spos >= 0) & (spos >= off[:, None])
            & (jnp.arange(slots)[None] != slot[:, None]))


def _decode_slot(cfg, t_vec, slots):
    """The cache slot each batch row's token at position ``t_vec`` takes:
    its position, or that position modulo a rolling window's slots."""
    return t_vec % slots if cfg.window > 0 else t_vec


def gqa_write(cfg, cache, rows, t):
    """Write one decode step's new rows into every layer of a layer-
    stacked GQA cache, in place.

    rows: {"k", "v": [L, B, 1, hkv, dh]}, the ``gqa_decode`` rows of all L
    layers; t: scalar or [B] int32 position.  Each batch row's K/V land in
    its slot of every layer (``kernels.kv_slot_update``) and the slot's
    ``slot_pos`` becomes ``t``.  The decode step calls this once, after
    its layer scan has read the whole stack, so the reads precede the
    write and nothing forces a copy of the stack."""
    b, slots = cache["slot_pos"].shape[1:]
    t_vec = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
    slot = _decode_slot(cfg, t_vec, slots)
    # every layer's slot_pos is the same row: one updated copy, broadcast
    # (a scatter spanning the layers has XLA relay the stack out and back)
    spos = cache["slot_pos"]
    spos = jnp.broadcast_to(spos[0].at[jnp.arange(b), slot].set(t_vec),
                            spos.shape)
    return {"k": kernel_ops.kv_slot_update(cache["k"], rows["k"], slot),
            "v": kernel_ops.kv_slot_update(cache["v"], rows["v"], slot),
            "slot_pos": spos}


# ------------------------------------------------------------ MLA module
def init_mla(key, cfg):
    ks = jax.random.split(key, 7)
    dt = cfg.jnp_dtype
    h = cfg.n_heads
    return {
        "w_dq": dense_init(ks[0], cfg.d_model, cfg.mla_q_lora, dt),
        "w_uq": dense_init(ks[1], cfg.mla_q_lora,
                           h * (cfg.mla_qk_nope + cfg.mla_qk_rope), dt),
        "w_dkv": dense_init(ks[2], cfg.d_model, cfg.mla_kv_lora, dt),
        "w_kr": dense_init(ks[3], cfg.d_model, cfg.mla_qk_rope, dt),
        "w_uk": dense_init(ks[4], cfg.mla_kv_lora, h * cfg.mla_qk_nope, dt),
        "w_uv": dense_init(ks[5], cfg.mla_kv_lora, h * cfg.mla_v_dim, dt),
        "wo": dense_init(ks[6], h * cfg.mla_v_dim, cfg.d_model, dt),
        "q_ln": jnp.zeros((cfg.mla_q_lora,), jnp.float32),
        "kv_ln": jnp.zeros((cfg.mla_kv_lora,), jnp.float32),
    }


@jax.named_scope("attention")
def mla_attention(p, cfg, x, *, pos, mca_key=None, return_cache=False,
                  kv_valid=None):
    """MLA (latent) attention, full-sequence. MCA applies to the latent
    value up-projection W_UV (importance = colmax) and W_O.

    kv_valid: optional [B, S] bool marking real (non-left-padding) tokens.
    """
    b, s, d = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.mla_qk_nope, cfg.mla_qk_rope, cfg.mla_v_dim
    scale = (dn + dr) ** -0.5
    stats = _zero_stats(cfg.mca.n_tiers)

    cq = rmsnorm(x @ p["w_dq"], p["q_ln"], cfg.norm_eps)
    q = _split_heads(cq @ p["w_uq"], h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)

    ckv = rmsnorm(x @ p["w_dkv"], p["kv_ln"], cfg.norm_eps)
    k_rope = apply_rope((x @ p["w_kr"])[:, :, None, :], pos,
                        cfg.rope_theta)                     # [B,S,1,dr]
    k_nope = _split_heads(ckv @ p["w_uk"], h, dn)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    qg = q_full.reshape(b, s, h, 1, dn + dr)                # hkv=h, g=1
    mesh = get_mesh()
    nm = mesh.shape.get("model", 1) if mesh is not None else 1
    if nm > 1 and cfg.attn_parallel in ("seq", "auto"):
        # MLA weights are replicated; run attention sequence-parallel
        k = constrain(k, DP, None, None, None)
        qg = constrain(qg, DP, "model", None, None, None)

    chunk = pick_chunk(s, cfg.attn_chunk)
    mca_v = cfg.mca.active("v_proj") and mca_key is not None
    if mca_v:
        m, lse = chunked_lse(qg, k, scale=scale, causal=cfg.causal,
                             window=0, chunk=chunk,
                             unroll=cfg.unroll_inner, kv_valid=kv_valid)
        colmax = chunked_colmax(qg, k, lse, scale=scale, causal=cfg.causal,
                                window=0, chunk=chunk,
                                unroll=cfg.unroll_inner, kv_valid=kv_valid,
                                q_valid=kv_valid)
        with jax.named_scope("mca"):
            hv, s_v = mca_project(jax.random.fold_in(mca_key, 1), ckv,
                                  p["w_uv"], colmax, s, cfg.mca, "v_proj")
        stats = _acc_stats(stats, s_v)
        v = _split_heads(hv, h, dv)
        out = chunked_av(qg, k, v, lse, scale=scale, causal=cfg.causal,
                         window=0, chunk=chunk, unroll=cfg.unroll_inner,
                         kv_valid=kv_valid)
        rowmax = jnp.exp(jnp.max(m - lse, axis=(1, 2)))
    else:
        v = _split_heads(ckv @ p["w_uv"], h, dv)
        out, m, lse = onepass_attention(qg, k, v, scale=scale,
                                        causal=cfg.causal, window=0,
                                        chunk=chunk,
                                        unroll=cfg.unroll_inner,
                                        kv_valid=kv_valid)
        rowmax = jnp.exp(jnp.max(m - lse, axis=(1, 2)))
    if kv_valid is not None:
        rowmax = jnp.where(kv_valid, rowmax, 0.0)

    out = out.reshape(b, s, h * dv)
    if cfg.mca.active("o_proj") and mca_key is not None:
        with jax.named_scope("mca"):
            y, s_o = mca_project(jax.random.fold_in(mca_key, 2), out,
                                 p["wo"], rowmax, s, cfg.mca, "o_proj")
        stats = _acc_stats(stats, s_o)
    else:
        y = out @ p["wo"]

    cache = (ckv, k_rope[:, :, 0, :]) if return_cache else None
    return y, cache, stats, rowmax


def init_mla_cache(cfg, batch, max_len, dtype):
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.mla_kv_lora), dtype),
        "kr": jnp.zeros((batch, max_len, cfg.mla_qk_rope), dtype),
    }


@jax.named_scope("attention")
def mla_decode(p, cfg, x, cache, *, t, layer, pos_off=None):
    """Absorbed-matrix MLA decode of layer ``layer`` of a layer-stacked
    latent cache: scores/value read the latent cache directly; per-token
    cache cost is (kv_lora + rope) floats.

    cache: {"ckv": [L, B, S, kv_lora], "kr": [L, B, S, rope]}, the whole
    stack, read and not written here.  As in :func:`gqa_decode` the query
    attends to the old cache plus the current token's own latents, joined
    in the softmax, and the new rows are returned for ``mla_write``.

    ``t`` may be a scalar (lockstep decode) or a [B] vector (per-slot
    continuous batching — each row writes/reads at its own position)."""
    b = x.shape[0]
    h = cfg.n_heads
    dn, dr, dv = cfg.mla_qk_nope, cfg.mla_qk_rope, cfg.mla_v_dim
    dl = cfg.mla_kv_lora
    scale = (dn + dr) ** -0.5
    dt = cache["ckv"].dtype
    off = jnp.zeros((b,), jnp.int32) if pos_off is None else pos_off
    t_vec = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))

    cq = rmsnorm(x @ p["w_dq"], p["q_ln"], cfg.norm_eps)
    q = _split_heads(cq @ p["w_uq"], h, dn + dr)            # [B,1,h,dn+dr]
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    posb = t_vec[:, None] - off[:, None]
    q_rope = apply_rope(q_rope, posb, cfg.rope_theta)

    ckv1 = rmsnorm(x @ p["w_dkv"], p["kv_ln"], cfg.norm_eps
                   ).astype(dt)                               # [B,1,dl]
    kr1 = apply_rope((x @ p["w_kr"])[:, :, None, :], posb,
                     cfg.rope_theta)[:, :, 0, :].astype(dt)   # [B,1,dr]
    ckv = cache["ckv"][layer]
    kr = cache["kr"][layer]

    # absorb W_UK into the query:  q_lat[b,h,dl] = q_nope . W_UK[:, h, :]
    w_uk = p["w_uk"].reshape(dl, h, dn)
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope, w_uk)

    # bf16 operands widen exactly, so f32 products are the bf16 ones
    f32 = jnp.float32
    q_lat, q_rope = q_lat.astype(f32), q_rope.astype(f32)
    s = (jnp.einsum("bqhl,bsl->bhqs", q_lat, ckv.astype(f32))
         + jnp.einsum("bqhd,bsd->bhqs", q_rope, kr.astype(f32))) * scale
    s1 = (jnp.einsum("bqhl,bql->bhq", q_lat, ckv1.astype(f32))
          + jnp.einsum("bqhd,bqd->bhq", q_rope, kr1.astype(f32))
          )[..., None] * scale                                # [B,h,1,1]
    idxs = jnp.arange(ckv.shape[1])
    valid = ((idxs[None, :] < t_vec[:, None])
             & (idxs[None, :] >= off[:, None]))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    s1 = jnp.where((t_vec >= off)[:, None, None, None], s1, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s1)
    e = jnp.exp(s - m)
    e1 = jnp.exp(s1 - m)
    l = jnp.sum(e, axis=-1, keepdims=True) + e1
    a, a1 = e / l, e1 / l
    out_lat = (jnp.einsum("bhqs,bsl->bqhl", a.astype(dt).astype(f32),
                          ckv.astype(f32))
               + jnp.einsum("bhqs,bsl->bqhl", a1.astype(dt).astype(f32),
                            ckv1.astype(f32)))
    out_lat = out_lat.astype(dt)
    # absorb W_UV on the way out
    w_uv = p["w_uv"].reshape(dl, h, dv)
    out = jnp.einsum("bqhl,lhv->bqhv", out_lat, w_uv).reshape(b, 1, h * dv)
    y = out @ p["wo"]
    rowmax = jnp.maximum(jnp.max(a, axis=(1, 3)), jnp.max(a1, axis=(1, 3)))
    return y, {"ckv": ckv1, "kr": kr1}, rowmax


def mla_write(cfg, cache, rows, t):
    """Write one decode step's new latent rows (``mla_decode``'s, of all L
    layers: {"ckv": [L, B, 1, kv_lora], "kr": [L, B, 1, rope]}) into every
    layer of the stacked latent cache at position ``t``, in place (see
    ``gqa_write``)."""
    del cfg
    b = cache["ckv"].shape[1]
    t_vec = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
    return {name: kernel_ops.kv_slot_update(cache[name], rows[name], t_vec)
            for name in ("ckv", "kr")}
