"""AdamW with decoupled weight decay, global-norm clipping, and
scan-based microbatch gradient accumulation. No external deps."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    schedule: Optional[Callable] = None       # step -> lr multiplier


def _decay_mask(path) -> bool:
    """Decay matmul weights; skip norms/biases/1-d params."""
    name = str(path[-1].key) if hasattr(path[-1], "key") else ""
    return name not in {"scale", "bias", "norm", "lam", "b_a", "b_i",
                        "a_log", "d_skip", "dt_bias", "q_norm", "k_norm",
                        "q_ln", "kv_ln", "conv_b"}


def init_state(params):
    # zeros_like keeps each leaf's sharding, so sharded params get moments
    # sharded the same way instead of a full f32 copy on one device
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    return {"m": zeros,
            "v": jax.tree.map(jnp.copy, zeros),
            "count": jnp.zeros((), jnp.int32)}


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads), norm


def apply_updates(cfg: AdamWConfig, params, grads, state):
    """One AdamW step. Returns (new_params, new_state, grad_norm).

    The clip scale is folded into the per-leaf update (not materialized as
    a clipped f32 grad tree) so the f32 cast happens at the ZeRO-sharded
    moment tensors — n_data-fold smaller than the parameter sharding.
    """
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))
    count = state["count"] + 1
    lr = cfg.lr * (cfg.schedule(count) if cfg.schedule else 1.0)
    b1c = 1.0 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** count.astype(jnp.float32)

    def upd(path, p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mh = m / b1c
        vh = v / b2c
        step = mh / (jnp.sqrt(vh) + cfg.eps)
        if cfg.weight_decay and _decay_mask(path):
            step = step + cfg.weight_decay * p.astype(jnp.float32)
        new_p = (p.astype(jnp.float32) - lr * step).astype(p.dtype)
        return new_p, m, v

    flat = jax.tree_util.tree_map_with_path(
        upd, params, grads, state["m"], state["v"])
    new_params = jax.tree.map(lambda t: t[0], flat,
                              is_leaf=lambda t: isinstance(t, tuple))
    new_m = jax.tree.map(lambda t: t[1], flat,
                         is_leaf=lambda t: isinstance(t, tuple))
    new_v = jax.tree.map(lambda t: t[2], flat,
                         is_leaf=lambda t: isinstance(t, tuple))
    return new_params, {"m": new_m, "v": new_v, "count": count}, gnorm


def cosine_schedule(warmup: int, total: int, min_frac: float = 0.1):
    def fn(step):
        step = step.astype(jnp.float32)
        warm = jnp.minimum(step / max(warmup, 1), 1.0)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = min_frac + (1 - min_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return warm * cos
    return fn


def accumulate_gradients(loss_fn, params, batch, n_micro: int, key=None):
    """Split the batch into ``n_micro`` microbatches and scan-accumulate
    grads — overlaps the DP gradient collectives with compute on TPU.

    loss_fn: (params, microbatch, key) -> (loss, metrics)."""
    if n_micro == 1:
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch, key)

    def split(x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])

    micro = jax.tree.map(split, batch)
    gfun = jax.value_and_grad(loss_fn, has_aux=True)

    def step(carry, inp):
        gsum, lsum = carry
        mb, i = inp
        k = None if key is None else jax.random.fold_in(key, i)
        (loss, metrics), g = gfun(params, mb, k)
        gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum, g)
        return (gsum, lsum + loss), metrics

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (gsum, lsum), metrics = jax.lax.scan(
        step, (g0, jnp.zeros(())), (micro, jnp.arange(n_micro)))
    grads = jax.tree.map(lambda g: g / n_micro, gsum)
    last_metrics = jax.tree.map(lambda m: m[-1], metrics)
    return (lsum / n_micro, last_metrics), grads
