"""Feed-forward blocks: dense (SwiGLU / GeLU) and sort-based MoE dispatch.

The MoE path uses sort-based dispatch (MaxText-style): top-k expert ids are
sorted, positions-within-expert computed from segment offsets, tokens
scattered into a static [E, C, d] buffer, expert matmuls run as one grouped
einsum, and results combine back weighted by the router gate.  One-hot
[n, E, C] dispatch tensors (GShard style) would be O(n^2)-ish at our token
counts; sort-based is O(nk log nk).

MoE + MCA (beyond-paper): the router gate probability is an a-priori
importance signal exactly like attention colmax, so expert up-projections
can run under the per-token Monte-Carlo estimator ("expert_ffn" site).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import amm, dispatch as mca_dispatch, schedule
from repro.dist.context import DP, constrain
from .common import dense_init, gelu


def _zero_stats():
    return {"exact_flops": jnp.zeros((), jnp.float32),
            "mca_flops": jnp.zeros((), jnp.float32)}


# ------------------------------------------------------------- dense FFN
def init_ffn(key, cfg):
    ks = jax.random.split(key, 3)
    dt = cfg.jnp_dtype
    p = {"w_up": dense_init(ks[0], cfg.d_model, cfg.d_ff, dt),
         "w_down": dense_init(ks[1], cfg.d_ff, cfg.d_model, dt)}
    if cfg.ffn_type == "swiglu":
        p["w_gate"] = dense_init(ks[2], cfg.d_model, cfg.d_ff, dt)
    return p


def ffn(p, cfg, x):
    if cfg.ffn_type == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = gelu(x @ p["w_up"])
    if cfg.attn_parallel != "dp":
        h = constrain(h, DP, None, "model")
    return h @ p["w_down"]


# ------------------------------------------------------------------- MoE
def init_moe(key, cfg):
    ks = jax.random.split(key, 4)
    dt = cfg.jnp_dtype
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    scale_in = 1.0 / jnp.sqrt(d)
    scale_out = 1.0 / jnp.sqrt(f)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_up": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                 * scale_in).astype(dt),
        "w_down": (jax.random.normal(ks[2], (e, f, d), jnp.float32)
                   * scale_out).astype(dt),
    }
    if cfg.ffn_type == "swiglu":
        p["w_gate"] = (jax.random.normal(ks[3], (e, d, f), jnp.float32)
                       * scale_in).astype(dt)
    return p


def moe_capacity(cfg, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * cfg.top_k * n_tokens / cfg.n_experts)
    return max(8, ((c + 7) // 8) * 8)


def moe_ffn(p, cfg, x, *, mca_key=None):
    """x: [B, S, d] -> (y, aux_loss, stats).

    Under a mesh this runs as shard-local dispatch inside shard_map: each
    (pod, data, model) shard routes its own tokens with local capacity and
    replicated expert weights (all-gathered at entry — experts here are
    small relative to dispatch traffic).  A global sort-based dispatch
    under GSPMD replicates [n*k, d] gathers across the mesh (measured
    ~180GB/device on granite train_4k); shard-local dispatch eliminates
    that entirely.  Without a mesh (tests/CPU) it is plain local dispatch.
    """
    from repro.dist.context import dp_axes, get_mesh
    mesh = get_mesh()
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        dp = dp_axes(mesh)
        dpe = dp[0] if len(dp) == 1 else dp
        n_dp = 1
        for a in dp:
            n_dp *= mesh.shape[a]
        nm = mesh.shape.get("model", 1)
        b, s, _ = x.shape
        batch_ok = b % n_dp == 0
        seq_ok = s % nm == 0
        if batch_ok:
            x_spec = P(dpe, "model" if seq_ok else None, None)
            key = (mca_key if mca_key is not None
                   else jax.random.PRNGKey(0))
            axes = tuple(a for a in mesh.axis_names
                         if a in dp or (seq_ok and a == "model"))

            def local_fn(p_l, x_l, key_l):
                y, aux, stats = _moe_local(p_l, cfg, x_l, key_l
                                           if mca_key is not None else None)
                aux = jax.lax.pmean(aux, axes)
                stats = jax.tree.map(lambda v: jax.lax.psum(v, axes), stats)
                return y, aux, stats

            return jax.shard_map(
                local_fn, mesh=mesh,
                in_specs=(P(), x_spec, P()),
                out_specs=(x_spec, P(), P()),
                check_vma=False)(p, x, key)
    return _moe_local(p, cfg, x, mca_key)


def _moe_local(p, cfg, x, mca_key=None):
    """Dispatch + expert compute over the (local) token set."""
    b, s, d = x.shape
    n = b * s
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(n, d)

    logits = (xf.astype(jnp.float32) @ p["router"])          # [n, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, k)                      # [n, k]
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)      # renormalize

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(eid, e, dtype=jnp.float32), axis=1), axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(me * ce / k)

    cap = moe_capacity(cfg, n)
    nk = n * k
    flat_e = eid.reshape(nk)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    flat_gate = gate.reshape(nk)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos = jnp.arange(nk) - starts[sorted_e]                  # rank in expert
    fit = pos < cap
    # scatter tokens into [E, C+1, d]; slot C is the overflow trash row
    slot = jnp.where(fit, pos, cap)
    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    buf = buf.at[sorted_e, slot].add(xf[flat_tok[order]])

    xe = buf[:, :cap]                                        # [E, C, d]
    stats = _zero_stats()
    if cfg.mca.active("expert_ffn") and mca_key is not None:
        h_up, st = _mca_expert_matmul(mca_key, cfg, xe, p["w_up"],
                                      sorted_e, slot, flat_gate[order],
                                      cap, s)
        stats = {"exact_flops": stats["exact_flops"] + st["exact_flops"],
                 "mca_flops": stats["mca_flops"] + st["mca_flops"]}
    else:
        h_up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"],
                          preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.ffn_type == "swiglu":
        h_gate = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"],
                            preferred_element_type=jnp.float32
                            ).astype(x.dtype)
        h = jax.nn.silu(h_gate) * h_up
    else:
        h = gelu(h_up)
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"],
                       preferred_element_type=jnp.float32).astype(x.dtype)

    # combine: gather each (token, k) result and weight by gate
    gathered = out_e[sorted_e, jnp.where(fit, pos, 0)]       # [nk, d]
    gathered = jnp.where(fit[:, None], gathered, 0.0)
    contrib = gathered * flat_gate[order][:, None].astype(x.dtype)
    y = jnp.zeros((n, d), x.dtype).at[flat_tok[order]].add(contrib)
    return y.reshape(b, s, d), aux, stats


def _mca_expert_matmul(key, cfg, xe, w_up, sorted_e, slot, gate_sorted,
                       cap, seq_len):
    """Per-expert Monte-Carlo up-projection driven by router gates.

    Importance of a dispatched slot is its gate probability; Eq. 9 turns it
    into a per-slot sample budget, evaluated with the per-token estimator
    vmapped over experts."""
    e, c, d = xe.shape
    f = w_up.shape[-1]
    block = cfg.mca.block_for(d)
    # importance per [E, C] slot (0 for unfilled slots -> min samples)
    imp = jnp.zeros((e, cap + 1), jnp.float32).at[sorted_e, slot].max(
        gate_sorted)[:, :cap]
    r_cols = schedule.r_cols_from_attention(imp, seq_len, cfg.mca.alpha, d)
    r_blocks = schedule.r_blocks_from_cols(r_cols, block)    # [E, C]

    keys = jax.random.split(key, e)
    out = jax.vmap(
        lambda kk, xx, ww, rr: mca_dispatch.per_token_mca_matmul(
            kk, xx, ww, rr, block))(keys, xe, w_up, r_blocks)
    mca_fl = amm.sampled_flops(r_blocks.reshape(-1), f, block)
    stats = {"exact_flops": jnp.asarray(amm.exact_flops(e * c, d, f),
                                        jnp.float32),
             "mca_flops": jnp.asarray(mca_fl, jnp.float32)}
    return out.astype(xe.dtype), stats
