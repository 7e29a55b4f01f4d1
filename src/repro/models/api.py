"""Top-level model API: build_model(cfg) -> Model(init/loss/prefill/decode).

Every assigned architecture is served through this one API; the launcher,
trainer, server, benchmarks and dry-run all consume Model objects.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from . import attention as attn
from . import ffn as ffn_mod
from . import rglru, ssm, stack
from .common import (apply_norm, dense_init, embed_tokens, init_embedding,
                     init_norm, maybe_scan, sinusoidal_pos_emb)
from .config import ModelConfig

NEG_INF = -1e30


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    init: Callable
    loss: Callable          # (params, batch, key|None) -> (loss, metrics)
    forward_hidden: Callable
    prefill: Callable       # (params, batch, max_len, key|None)
                            #   -> (cache, hidden, stats)
    decode: Callable        # (params, tokens, cache, t) -> (logits, cache)
    init_cache: Callable    # (batch, max_len) -> cache pytree


# ------------------------------------------------------------------ loss
@jax.named_scope("logits")
def chunked_xent(hidden, head, labels, cfg):
    """Sequence-chunked vocab-masked cross entropy.

    hidden: [B, S, d]; head: [d, Vp]; labels: [B, S] int32 (-1 = ignore).
    Keeps the [B, chunk, Vp] logits buffer bounded so 256k vocabs fit.
    """
    b, s, d = hidden.shape
    vp = head.shape[-1]
    chunk = attn.pick_chunk(s, cfg.logits_chunk)
    nc = s // chunk
    vocab_ok = (jnp.arange(vp) < cfg.vocab_size)

    def step(carry, inp):
        tot, cnt = carry
        h_c, y_c = inp                                     # [B,c,d], [B,c]
        logits = jnp.einsum("bcd,dv->bcv", h_c.astype(jnp.float32),
                            head.astype(jnp.float32))
        logits = jnp.where(vocab_ok[None, None], logits, NEG_INF)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(y_c, 0)[..., None], axis=-1)[..., 0]
        mask = (y_c >= 0).astype(jnp.float32)
        tot += jnp.sum((lse - ll) * mask)
        cnt += jnp.sum(mask)
        return (tot, cnt), None

    hs = jnp.moveaxis(hidden.reshape(b, nc, chunk, d), 1, 0)
    ys = jnp.moveaxis(labels.reshape(b, nc, chunk), 1, 0)
    (tot, cnt), _ = maybe_scan(jax.checkpoint(step),
                               (jnp.zeros(()), jnp.zeros(())),
                               (hs, ys), cfg.unroll_inner)
    return tot / jnp.maximum(cnt, 1.0)


def _head(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"]["table"].T
    return params["lm_head"]


@jax.named_scope("logits")
def _logits(params, cfg, hidden):
    logits = jnp.einsum("...d,dv->...v", hidden.astype(jnp.float32),
                        _head(params, cfg).astype(jnp.float32))
    vp = logits.shape[-1]
    return jnp.where(jnp.arange(vp) < cfg.vocab_size, logits, NEG_INF)


# ==================================================== decoder-only LM ====
def _init_lm(key, cfg):
    ks = jax.random.split(key, 4)
    kind = stack.layer_kind(cfg)
    params = {"embed": init_embedding(ks[0], cfg),
              "final_norm": init_norm(cfg)}
    if cfg.family == "hybrid":
        params["layers"] = stack.init_hybrid(ks[1], cfg)
    else:
        params["layers"] = stack.init_stack(ks[1], cfg, cfg.n_layers, kind)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.padded_vocab,
                                       cfg.jnp_dtype)
    if cfg.family == "vlm":
        params["patch_proj"] = dense_init(ks[3], cfg.d_model, cfg.d_model,
                                          cfg.jnp_dtype)
    return params


def _lm_embed(params, cfg, batch):
    x = embed_tokens(params["embed"], batch["tokens"])
    if cfg.family == "vlm" and "patches" in batch:
        px = batch["patches"].astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([px, x], axis=1)
    if cfg.add_sinusoidal_pos:
        pe = sinusoidal_pos_emb(x.shape[1], cfg.d_model, x.dtype)
        if "pos_offset" in batch:
            # left-padded rows: embedding index counts from the first real
            # token (pad rows clip to index 0; they are masked downstream)
            idx = jnp.clip(jnp.arange(x.shape[1])[None]
                           - batch["pos_offset"][:, None].astype(jnp.int32),
                           0, None)
            x = x + pe[idx]
        else:
            x = x + pe[None]
    return x


def _lm_hidden(params, cfg, batch, mca_key):
    x = _lm_embed(params, cfg, batch)
    pos = jnp.arange(x.shape[1])[None]
    if cfg.family == "hybrid":
        x, aux, stats = stack.hybrid_forward(params["layers"], cfg, x,
                                             pos=pos, mca_key=mca_key)
    else:
        x, aux, stats = stack.stack_forward(
            params["layers"], cfg, x, pos=pos, mca_key=mca_key,
            kind=stack.layer_kind(cfg))
    x = apply_norm(params["final_norm"], cfg, x)
    return x, aux, stats


def _lm_loss(params, cfg, batch, mca_key=None):
    hidden, aux, stats = _lm_hidden(params, cfg, batch, mca_key)
    if cfg.family == "vlm" and "patches" in batch:
        hidden = hidden[:, batch["patches"].shape[1]:]
    loss = chunked_xent(hidden, _head(params, cfg), batch["labels"], cfg)
    metrics = {"loss": loss, "aux_loss": aux,
               "mca_exact_flops": stats["exact_flops"],
               "mca_flops": stats["mca_flops"],
               "mca_tier_hist": stats["tier_hist"]}
    return loss + aux, metrics


# ----------------------------------------------------------- cache utils
def _pad_seq_cache(arr, slots: int):
    """arr: [B, S, ...] -> ([B, slots, ...], slot_pos [B, slots])."""
    b, s = arr.shape[0], arr.shape[1]
    if slots >= s:                                   # global cache
        pad = [(0, 0)] * arr.ndim
        pad[1] = (0, slots - s)
        out = jnp.pad(arr, pad)
        slot_pos = jnp.where(jnp.arange(slots) < s,
                             jnp.arange(slots), -1).astype(jnp.int32)
    else:                                            # rolling window cache
        tail = arr[:, s - slots:]
        pos = jnp.arange(s - slots, s)
        slot = pos % slots
        out = jnp.zeros((b, slots) + arr.shape[2:], arr.dtype
                        ).at[:, slot].set(tail)
        slot_pos = jnp.zeros((slots,), jnp.int32).at[slot].set(pos)
    # slot_pos is per-row so per-slot insertion can splice one request's
    # position state without touching its batch neighbours
    return out, jnp.broadcast_to(slot_pos[None], (b, slots))


def _gqa_prefill_cache(cfg, k, v, max_len, window):
    slots = window if window > 0 else max_len
    kc, slot_pos = _pad_seq_cache(k, slots)
    vc, _ = _pad_seq_cache(v, slots)
    return {"k": kc, "v": vc, "slot_pos": slot_pos}


def cache_insert_slot(cache, new, slot):
    """Splice a batch-1 prefill cache into row ``slot`` of a live cache.

    ``cache`` is a batched LM decode cache (`{"layers": ..., "pos_off":
    [B]}` with every layer leaf scan-stacked `[L, 1-or-B, ...]`, batch on
    axis 1); ``new`` is the same structure from a batch=1 prefill at the
    same ``max_len``.  ``slot`` may be a traced int32 — the splice is a
    ``dynamic_update_slice`` per leaf, so occupied rows keep decoding
    undisturbed while the freed row admits the next request (per-slot
    continuous batching).
    """
    layers = jax.tree.map(
        lambda c, n: jax.lax.dynamic_update_slice_in_dim(
            c, n.astype(c.dtype), slot, axis=1),
        cache["layers"], new["layers"])
    out = {"layers": layers}
    if "pos_off" in cache:
        off = new.get("pos_off")
        if off is None:
            off = jnp.zeros((1,), jnp.int32)
        out["pos_off"] = jax.lax.dynamic_update_slice_in_dim(
            cache["pos_off"], off.astype(jnp.int32), slot, axis=0)
    return out


# -------------------------------------------------- LM prefill / decode
def _lm_prefill(params, cfg, batch, max_len, mca_key=None):
    """Run the full prompt, return (cache, last_hidden, stats).

    batch may carry "pos_offset" [B] int32 left-padding amounts (number of
    pad tokens at the front of each row). Offsets shift RoPE/positions to
    count from the first real token and mask padding keys everywhere, so a
    left-padded row generates exactly as it would alone.
    """
    x = _lm_embed(params, cfg, batch)
    b, s = x.shape[0], x.shape[1]
    kind = stack.layer_kind(cfg)

    off = batch.get("pos_offset")
    if off is None:
        pos = jnp.arange(s)[None]
        kv_valid = None
        off_arr = jnp.zeros((b,), jnp.int32)
    else:
        if kind == "ssm" or cfg.family in ("hybrid", "vlm"):
            raise NotImplementedError(
                f"pos_offset prefill is not supported for {cfg.family!r} "
                "models (recurrent state has no padding mask)")
        off_arr = off.astype(jnp.int32)
        pos = jnp.arange(s)[None] - off_arr[:, None]
        kv_valid = jnp.arange(s)[None] >= off_arr[:, None]

    if cfg.family == "hybrid":
        cache, hid, stats = _hybrid_prefill(params, cfg, x, pos, max_len,
                                            mca_key)
        return cache, hid, stats

    def body(carry, inp):
        xx, stats = carry
        p_l, idx = inp
        key_l = None if mca_key is None else jax.random.fold_in(mca_key, idx)
        h = apply_norm(p_l["ln1"], cfg, xx)
        if kind == "ssm":
            y, state, conv_tail = ssm.mamba2_forward(p_l["mixer"], cfg, h,
                                                     return_state=True)
            xx = xx + y
            cache_l = {"state": state, "conv": conv_tail}
        elif cfg.attn_type == "mla":
            y, (ckv, kr), st, _ = attn.mla_attention(
                p_l["mixer"], cfg, h, pos=pos, mca_key=key_l,
                return_cache=True, kv_valid=kv_valid)
            stats = stack._add_stats(stats, st)
            xx = xx + y
            ckv_p, _ = _pad_seq_cache(ckv, max_len)
            kr_p, _ = _pad_seq_cache(kr, max_len)
            cache_l = {"ckv": ckv_p, "kr": kr_p}
        else:
            y, (k, v), st, _ = attn.gqa_attention(
                p_l["mixer"], cfg, h, pos=pos, mca_key=key_l,
                return_kv=True,
                pos_offset=None if off is None else off_arr)
            stats = stack._add_stats(stats, st)
            xx = xx + y
            cache_l = _gqa_prefill_cache(cfg, k, v, max_len, cfg.window)
        if kind != "ssm":
            h = apply_norm(p_l["ln2"], cfg, xx)
            if kind == "attn_moe":
                y, _, st = ffn_mod.moe_ffn(p_l["ffn"], cfg, h,
                                           mca_key=key_l)
                stats = stack._add_stats(stats, st)
            else:
                y = ffn_mod.ffn(p_l["ffn"], cfg, h)
            xx = xx + y
        return (xx, stats), cache_l

    (x, stats), caches = maybe_scan(
        body, (x, stack._zero_carry_stats(cfg)),
        (params["layers"], jnp.arange(cfg.n_layers)),
        cfg.unroll_layers)
    x = apply_norm(params["final_norm"], cfg, x)
    return {"layers": caches, "pos_off": off_arr}, x, stats


def _decode_layer(p_l, cfg, xx, cache, layer, t, kind, pos_off=None,
                  visible=None):
    """Decode one token through layer ``layer`` of a layer-stacked cache
    (every leaf ``[L, ...]``).  Returns (x, cache, rows).

    Attention reads the layer's K/V from the stack and returns the new
    rows, which ``_write_rows`` puts into every layer at once after the
    layer scan (rows is None otherwise); GQA attends the slots
    ``visible`` (``attention.gqa_visible``, computed once per step).  A
    recurrent layer, whose whole state changes every step, reads its
    slice and writes it back at the same index of the carried stack."""
    h = apply_norm(p_l["ln1"], cfg, xx)
    rows = None
    if kind in ("ssm", "rec_ffn"):
        decode = (ssm.mamba2_decode if kind == "ssm"
                  else rglru.recurrent_decode)
        y, new_l = decode(p_l["mixer"], cfg, h,
                          jax.tree.map(lambda c: c[layer], cache))
        cache = jax.tree.map(
            lambda c, n: jax.lax.dynamic_update_index_in_dim(
                c, n.astype(c.dtype), layer, 0), cache, new_l)
        xx = xx + y
        if kind == "ssm":
            return xx, cache, rows
    elif cfg.attn_type == "mla":
        y, rows, _ = attn.mla_decode(p_l["mixer"], cfg, h, cache, t=t,
                                     layer=layer, pos_off=pos_off)
        xx = xx + y
    else:
        y, rows, _ = attn.gqa_decode(p_l["mixer"], cfg, h, cache, t=t,
                                     layer=layer, visible=visible,
                                     pos_off=pos_off)
        xx = xx + y
    h = apply_norm(p_l["ln2"], cfg, xx)
    if kind == "attn_moe":
        y, _, _ = ffn_mod.moe_ffn(p_l["ffn"], cfg, h)
    else:
        y = ffn_mod.ffn(p_l["ffn"], cfg, h)
    return xx + y, cache, rows


def _write_rows(cfg, cache, rows, t):
    """Put a decode step's new attention rows (leaves ``[L, B, 1, ...]``)
    into the stacked cache in place; a recurrent stack has none."""
    if rows is None:
        return cache
    write = attn.mla_write if cfg.attn_type == "mla" else attn.gqa_write
    return write(cfg, cache, rows, t)


def _visible(cfg, cache, t, kind, pos_off=None):
    """The step's attendable slots for a stacked GQA cache, else None."""
    if kind in ("ssm", "rec_ffn") or cfg.attn_type == "mla":
        return None
    return attn.gqa_visible(cfg, cache, t, pos_off)


def _decode_one_layer(p_l, cfg, xx, cache_l, t, kind):
    """:func:`_decode_layer` on one layer's own (unstacked) cache."""
    cache = jax.tree.map(lambda c: c[None], cache_l)
    xx, cache, rows = _decode_layer(p_l, cfg, xx, cache, 0, t, kind,
                                    visible=_visible(cfg, cache, t, kind))
    if rows is not None:
        cache = _write_rows(cfg, cache, jax.tree.map(lambda r: r[None], rows),
                            t)
    return xx, jax.tree.map(lambda c: c[0], cache)


def _lm_decode(params, cfg, tokens, cache, t):
    """tokens: [B, 1]; t: scalar or [B] int32. Returns (logits, cache).

    The layer-stacked cache stays one set of buffers through the step:
    the layer scan reads each layer's slice of it (a recurrent layer also
    writes its state back in place) and yields the attention layers' new
    rows, which are then written into the stack in place, B rows per
    layer.  Nothing cuts the stack into per-layer ``xs`` and stacks
    fresh ``ys`` again, so the step never copies the stack."""
    x = embed_tokens(params["embed"], tokens)
    kind = stack.layer_kind(cfg)
    if cfg.family == "hybrid":
        return _hybrid_decode(params, cfg, x, cache, t)
    pos_off = cache.get("pos_off")
    visible = _visible(cfg, cache["layers"], t, kind, pos_off)

    def body(carry, inp):
        xx, caches = carry
        p_l, layer = inp
        xx, caches, rows = _decode_layer(p_l, cfg, xx, caches, layer, t,
                                         kind, pos_off, visible)
        return (xx, caches), rows

    (x, new_caches), rows = maybe_scan(
        body, (x, cache["layers"]),
        (params["layers"], jnp.arange(cfg.n_layers)), cfg.unroll_layers)
    new = {"layers": _write_rows(cfg, new_caches, rows, t)}
    x = apply_norm(params["final_norm"], cfg, x)
    if pos_off is not None:
        new["pos_off"] = pos_off
    return _logits(params, cfg, x), new


def _lm_init_cache(cfg, batch, max_len):
    kind = stack.layer_kind(cfg)
    dt = cfg.jnp_dtype

    def one():
        if kind == "ssm":
            return ssm.init_mamba2_cache(cfg, batch, dt)
        if cfg.attn_type == "mla":
            return attn.init_mla_cache(cfg, batch, max_len, dt)
        return attn.init_gqa_cache(cfg, batch, max_len, dt)

    caches = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[one() for _ in range(cfg.n_layers)])
    return {"layers": caches, "pos_off": jnp.zeros((batch,), jnp.int32)}


# ------------------------------------------------------- hybrid variants
def _hybrid_prefill(params, cfg, x, pos, max_len, mca_key):
    n_groups, pat, rem = stack.hybrid_layout(cfg)

    def make_cache(p_l, xx, stats, kind, key_l):
        h = apply_norm(p_l["ln1"], cfg, xx)
        if kind == "rec_ffn":
            y, conv_tail, h_fin = rglru.recurrent_block_with_state(
                p_l["mixer"], cfg, h)
            xx = xx + y
            cache_l = {"h": h_fin, "conv": conv_tail}
        else:
            y, (k, v), st, _ = attn.gqa_attention(
                p_l["mixer"], cfg, h, pos=pos, mca_key=key_l,
                window=cfg.window, return_kv=True)
            stats = stack._add_stats(stats, st)
            xx = xx + y
            cache_l = _gqa_prefill_cache(cfg, k, v, max_len, cfg.window)
        h = apply_norm(p_l["ln2"], cfg, xx)
        xx = xx + ffn_mod.ffn(p_l["ffn"], cfg, h)
        return xx, stats, cache_l

    def body(carry, inp):
        xx, stats = carry
        gp, gidx = inp
        caches = {}
        for i, kind in enumerate(pat):
            key_l = None if mca_key is None else jax.random.fold_in(
                mca_key, gidx * len(pat) + i)
            xx, stats, caches[f"pos{i}"] = make_cache(gp[f"pos{i}"], xx,
                                                      stats, kind, key_l)
        return (xx, stats), caches

    (x, stats), gcaches = maybe_scan(
        body, (x, stack._zero_carry_stats(cfg)),
        (params["layers"]["groups"], jnp.arange(n_groups)),
        cfg.unroll_layers)
    rem_caches = []
    for i, kind in enumerate(rem):
        key_l = None if mca_key is None else jax.random.fold_in(
            mca_key, n_groups * len(pat) + i)
        x, stats, c = make_cache(params["layers"]["rem"][i], x, stats,
                                 kind, key_l)
        rem_caches.append(c)
    x = apply_norm(params["final_norm"], cfg, x)
    return {"groups": gcaches, "rem": rem_caches}, x, stats


def _hybrid_decode(params, cfg, x, cache, t):
    n_groups, pat, rem = stack.hybrid_layout(cfg)

    def body(xx, inp):
        gp, gc = inp
        new_c = {}
        for i, kind in enumerate(pat):
            xx, new_c[f"pos{i}"] = _decode_one_layer(gp[f"pos{i}"], cfg, xx,
                                                     gc[f"pos{i}"], t, kind)
        return xx, new_c

    x, gcaches = maybe_scan(body, x, (params["layers"]["groups"],
                                      cache["groups"]),
                            cfg.unroll_layers)
    rem_caches = []
    for i, kind in enumerate(rem):
        x, c = _decode_one_layer(params["layers"]["rem"][i], cfg, x,
                                 cache["rem"][i], t, kind)
        rem_caches.append(c)
    x = apply_norm(params["final_norm"], cfg, x)
    return _logits(params, cfg, x), {"groups": gcaches, "rem": rem_caches}


def _hybrid_init_cache(cfg, batch, max_len):
    n_groups, pat, rem = stack.hybrid_layout(cfg)
    dt = cfg.jnp_dtype

    def one(kind):
        if kind == "rec_ffn":
            return rglru.init_recurrent_cache(cfg, batch, dt)
        return attn.init_gqa_cache(cfg, batch, max_len, dt)

    groups = {
        f"pos{i}": jax.tree.map(lambda *xs: jnp.stack(xs),
                                *[one(kind) for _ in range(n_groups)])
        for i, kind in enumerate(pat)}
    return {"groups": groups, "rem": [one(k) for k in rem]}


# ====================================================== encoder-decoder ==
def _init_encdec(key, cfg):
    ks = jax.random.split(key, 5)
    params = {
        "embed": init_embedding(ks[0], cfg),
        "enc_layers": stack.init_stack(ks[1], cfg, cfg.n_encoder_layers,
                                       "attn_ffn"),
        "enc_norm": init_norm(cfg),
        "dec_layers": stack.init_stack(ks[2], cfg, cfg.n_layers,
                                       "dec_attn_ffn"),
        "final_norm": init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[3], cfg.d_model, cfg.padded_vocab,
                                       cfg.jnp_dtype)
    return params


def _encode(params, cfg, frames, mca_key):
    x = frames.astype(cfg.jnp_dtype)
    x = x + sinusoidal_pos_emb(x.shape[1], cfg.d_model,
                               x.dtype)[None]
    pos = jnp.arange(x.shape[1])[None]
    x, _, stats = stack.stack_forward(
        params["enc_layers"], cfg, x, pos=pos, mca_key=mca_key,
        kind="attn_ffn", causal=False, window=0)
    return apply_norm(params["enc_norm"], cfg, x), stats


def _encdec_hidden(params, cfg, batch, mca_key):
    enc_key = None if mca_key is None else jax.random.fold_in(mca_key, 101)
    enc_out, enc_stats = _encode(params, cfg, batch["frames"], enc_key)
    x = embed_tokens(params["embed"], batch["tokens"])
    x = x + sinusoidal_pos_emb(x.shape[1], cfg.d_model, x.dtype)[None]
    pos = jnp.arange(x.shape[1])[None]
    x, aux, stats = stack.stack_forward(
        params["dec_layers"], cfg, x, pos=pos, mca_key=mca_key,
        kind="dec_attn_ffn", enc_out=enc_out, causal=True, window=0)
    stats = {k: stats[k] + enc_stats[k] for k in stats}
    x = apply_norm(params["final_norm"], cfg, x)
    return x, aux, stats, enc_out


def _encdec_loss(params, cfg, batch, mca_key=None):
    hidden, aux, stats, _ = _encdec_hidden(params, cfg, batch, mca_key)
    loss = chunked_xent(hidden, _head(params, cfg), batch["labels"], cfg)
    return loss + aux, {"loss": loss, "aux_loss": aux,
                        "mca_exact_flops": stats["exact_flops"],
                        "mca_flops": stats["mca_flops"]}


def _encdec_prefill(params, cfg, batch, max_len, mca_key=None):
    if batch.get("pos_offset") is not None:
        raise NotImplementedError(
            "pos_offset prefill is not supported for encoder-decoder models")
    enc_key = None if mca_key is None else jax.random.fold_in(mca_key, 101)
    enc_out, _ = _encode(params, cfg, batch["frames"], enc_key)
    x = embed_tokens(params["embed"], batch["tokens"])
    x = x + sinusoidal_pos_emb(x.shape[1], cfg.d_model, x.dtype)[None]
    pos = jnp.arange(x.shape[1])[None]

    def body(carry, inp):
        xx, stats = carry
        p_l, idx = inp
        key_l = None if mca_key is None else jax.random.fold_in(mca_key, idx)
        h = apply_norm(p_l["ln1"], cfg, xx)
        y, (k, v), st, _ = attn.gqa_attention(p_l["mixer"], cfg, h, pos=pos,
                                              mca_key=key_l, return_kv=True)
        stats = stack._add_stats(stats, st)
        xx = xx + y
        self_cache = _gqa_prefill_cache(cfg, k, v, max_len, 0)
        h = apply_norm(p_l["ln_x"], cfg, xx)
        y, (ck, cv), st, _ = attn.gqa_attention(
            p_l["cross"], cfg, h, pos=pos, mca_key=key_l, causal=False,
            window=0, kv_x=enc_out, return_kv=True)
        stats = stack._add_stats(stats, st)
        xx = xx + y
        h = apply_norm(p_l["ln2"], cfg, xx)
        xx = xx + ffn_mod.ffn(p_l["ffn"], cfg, h)
        return (xx, stats), {"self": self_cache, "cross_k": ck,
                             "cross_v": cv}

    (x, stats), caches = maybe_scan(
        body, (x, stack._zero_carry_stats(cfg)),
        (params["dec_layers"], jnp.arange(cfg.n_layers)),
        cfg.unroll_layers)
    x = apply_norm(params["final_norm"], cfg, x)
    return {"layers": caches}, x, stats


@jax.named_scope("attention")
def _cross_decode(p, cfg, x, ck, cv):
    """One-query cross attention against cached encoder K/V."""
    b = x.shape[0]
    hkv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    dh = cfg.d_head
    q = (x @ p["wq"]).reshape(b, 1, hkv, g, dh)
    s = jnp.einsum("bqhgd,bshd->bhgqs", q, ck,
                   preferred_element_type=jnp.float32) * dh ** -0.5
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", a.astype(cv.dtype), cv)
    return out.reshape(b, 1, cfg.n_heads * dh) @ p["wo"]


def _encdec_decode(params, cfg, tokens, cache, t):
    x = embed_tokens(params["embed"], tokens)
    pe = sinusoidal_pos_emb(cache["layers"]["self"]["k"].shape[2],
                            cfg.d_model, x.dtype)
    x = x + jax.lax.dynamic_slice_in_dim(pe, t, 1)[None]

    def body(xx, inp):
        p_l, cache_l = inp
        h = apply_norm(p_l["ln1"], cfg, xx)
        self_c = jax.tree.map(lambda c: c[None], cache_l["self"])
        y, rows, _ = attn.gqa_decode(
            p_l["mixer"], cfg, h, self_c, t=t, layer=0,
            visible=attn.gqa_visible(cfg, self_c, t))
        new_self = jax.tree.map(
            lambda c: c[0], attn.gqa_write(
                cfg, self_c, jax.tree.map(lambda r: r[None], rows), t))
        xx = xx + y
        h = apply_norm(p_l["ln_x"], cfg, xx)
        xx = xx + _cross_decode(p_l["cross"], cfg, h, cache_l["cross_k"],
                                cache_l["cross_v"])
        h = apply_norm(p_l["ln2"], cfg, xx)
        xx = xx + ffn_mod.ffn(p_l["ffn"], cfg, h)
        return xx, {"self": new_self, "cross_k": cache_l["cross_k"],
                    "cross_v": cache_l["cross_v"]}

    x, new_caches = maybe_scan(body, x, (params["dec_layers"],
                                         cache["layers"]),
                               cfg.unroll_layers)
    x = apply_norm(params["final_norm"], cfg, x)
    return _logits(params, cfg, x), {"layers": new_caches}


def _encdec_init_cache(cfg, batch, max_len):
    dt = cfg.jnp_dtype

    def one():
        return {
            "self": attn.init_gqa_cache(cfg, batch, max_len, dt),
            "cross_k": jnp.zeros((batch, cfg.encoder_len, cfg.n_kv_heads,
                                  cfg.d_head), dt),
            "cross_v": jnp.zeros((batch, cfg.encoder_len, cfg.n_kv_heads,
                                  cfg.d_head), dt),
        }

    caches = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[one() for _ in range(cfg.n_layers)])
    return {"layers": caches}


# ================================================================ factory
def build_model(cfg: ModelConfig) -> Model:
    if cfg.is_encoder_decoder:
        return Model(
            cfg=cfg,
            init=lambda key: _init_encdec(key, cfg),
            loss=lambda p, b, key=None: _encdec_loss(p, cfg, b, key),
            forward_hidden=lambda p, b, key=None: _encdec_hidden(
                p, cfg, b, key)[:3],
            prefill=lambda p, b, max_len, key=None: _encdec_prefill(
                p, cfg, b, max_len, key),
            decode=lambda p, tok, cache, t: _encdec_decode(
                p, cfg, tok, cache, t),
            init_cache=lambda batch, max_len: _encdec_init_cache(
                cfg, batch, max_len),
        )
    init_cache = (_hybrid_init_cache if cfg.family == "hybrid"
                  else _lm_init_cache)
    return Model(
        cfg=cfg,
        init=lambda key: _init_lm(key, cfg),
        loss=lambda p, b, key=None: _lm_loss(p, cfg, b, key),
        forward_hidden=lambda p, b, key=None: _lm_hidden(p, cfg, b, key),
        prefill=lambda p, b, max_len, key=None: _lm_prefill(
            p, cfg, b, max_len, key),
        decode=lambda p, tok, cache, t: _lm_decode(p, cfg, tok, cache, t),
        init_cache=lambda batch, max_len: init_cache(cfg, batch, max_len),
    )
