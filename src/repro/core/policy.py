"""MCAPolicy: where/how Monte-Carlo projection runs inside a model.

``mca_project`` is the single entry point models use for any projection
that has an a-priori importance signal (attention colmax, router prob, ...).
It implements the full paper pipeline:

    importance -> Eq.9 r schedule -> tier quantization -> capacity routing
               -> block-sampled matmuls (per tier)      [mode="tiered"]
               -> per-token i.i.d. estimator            [mode="per_token"]

and returns (y, stats) where stats carries the paper's FLOPs accounting.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import devtel

from . import amm, dispatch, schedule

Stats = dict


@dataclasses.dataclass(frozen=True)
class MCAConfig:
    """User-facing MCA knobs. ``alpha`` is the paper's single error knob."""
    enabled: bool = False
    alpha: float = 0.2
    block: int = 128
    n_tiers: int = 4
    r_min_blocks: int = 1
    mode: str = "tiered"            # "tiered" | "per_token"
    # static capacity fractions (of token count) per tier, cheap->exact;
    # tier 0 is always unbounded. Calibrate per workload (benchmarks do).
    capacity_fracs: Tuple[float, ...] = (1.0, 0.5, 0.375, 0.25)
    sites: Tuple[str, ...] = ("v_proj", "o_proj")
    use_kernel: bool = False        # route per-tier matmuls to Pallas kernel
    fast_colmax: bool = False       # fuse a conservative colmax into the
                                    # lse pass (saves one O(S^2) sweep;
                                    # over-allocates samples, bound intact)

    def active(self, site: str) -> bool:
        return self.enabled and site in self.sites

    def block_for(self, d: int) -> int:
        b = min(self.block, d)
        while d % b != 0:
            b //= 2
        return max(b, 1)


def _caps_for(n_tokens: int, n_tiers: int, fracs: Tuple[float, ...]) -> Tuple[int, ...]:
    caps = []
    for t in range(n_tiers):
        if t == 0:
            caps.append(n_tokens)
        else:
            frac = fracs[min(t, len(fracs) - 1)]
            caps.append(max(1, int(round(frac * n_tokens))))
    return tuple(caps)


def exact_project(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def mca_project(key: Optional[jax.Array], x: jax.Array, w: jax.Array,
                importance: Optional[jax.Array], seq_len: int,
                cfg: MCAConfig, site: str,
                matmul_impl: Optional[Callable] = None
                ) -> Tuple[jax.Array, Stats]:
    """Project ``x @ w`` under the MCA policy.

    x: [..., n, d] (leading dims flattened internally)
    w: [d, f]
    importance: [..., n] non-negative (attention colmax / router prob);
        None or inactive site -> exact matmul.
    seq_len: the ``n`` of Eq. 9 (sequence length of the attention matrix).
    """
    lead = x.shape[:-2]
    n, d = x.shape[-2], x.shape[-1]
    f = w.shape[-1]
    flat_n = math.prod(lead) * n
    exact_fl = amm.exact_flops(flat_n, d, f)

    if not cfg.active(site) or importance is None or key is None:
        y = exact_project(x, w)
        return y, {"site": site, "exact_flops": exact_fl,
                   "mca_flops": exact_fl, "tokens": flat_n}

    block = cfg.block_for(d)
    k = d // block
    ladder = schedule.tier_ladder(d, block, cfg.n_tiers, cfg.r_min_blocks)

    x2 = x.reshape(flat_n, d)
    imp = importance.reshape(flat_n)
    r_cols = schedule.r_cols_from_attention(imp, seq_len, cfg.alpha, d)
    r_blocks = schedule.r_blocks_from_cols(r_cols, block)
    tier = schedule.assign_tiers(r_blocks, ladder)

    if cfg.mode == "per_token":
        y2 = dispatch.per_token_mca_matmul(key, x2, w, r_blocks, block)
        mca_fl = amm.sampled_flops(r_blocks, f, block)
        hist = dispatch.tier_histogram(tier, len(ladder))
    else:
        y2, hist = _tiered_maybe_sharded(key, x2, w, tier, imp, ladder,
                                         cfg, block)
        ladder_arr = jnp.asarray(ladder, jnp.int32)
        mca_fl = jnp.sum(hist * 2 * ladder_arr * block * f)

    y = y2.reshape(*lead, n, f)
    # Device-side tier occupancy: emitted once per *execution* (vs the
    # stats pytree, which the host reads once per step) so a decode scan
    # accumulates every iteration's routing. No-op unless devtel enabled.
    devtel.emit_vec(
        tuple(f"mca.device_tier_hist.t{i}" for i in range(len(ladder))),
        hist)
    stats = {"site": site, "exact_flops": exact_fl, "mca_flops": mca_fl,
             "tokens": flat_n, "tier_hist": hist,
             "mean_r_blocks": jnp.mean(r_blocks.astype(jnp.float32)),
             "ladder": ladder}
    return y, stats


def _tiered_maybe_sharded(key, x2, w, tier, imp, ladder, cfg, block):
    """Tiered dispatch, shard-local under a mesh.

    Capacity routing sorts tokens by importance; a *global* sort over a
    sharded token axis lowers to giant collectives, so under a mesh each
    shard routes its own tokens with local capacities (exactly like the
    MoE dispatch) inside shard_map.  Statistics are psum'd back.
    """
    from repro.dist.context import dp_axes, get_mesh
    n_tiers = len(ladder)
    mesh = get_mesh()
    flat_n = x2.shape[0]
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        axes = tuple(a for a in mesh.axis_names)
        n_all = mesh.size
        if flat_n % n_all == 0:
            caps = _caps_for(flat_n // n_all, n_tiers, cfg.capacity_fracs)

            def local(x_l, tier_l, imp_l, key_l, w_l):
                # key enters replicated (spec P()); fold the shard's linear
                # index in so each shard draws independent block samples —
                # otherwise estimator errors are perfectly correlated along
                # the token axis and variance does not shrink with mesh size.
                lin = 0
                for a in axes:
                    lin = lin * mesh.shape[a] + jax.lax.axis_index(a)
                key_l = jax.random.fold_in(key_l, lin)
                tier_r = dispatch.apply_capacity(tier_l, imp_l, caps)
                y_l = dispatch.tiered_mca_matmul(
                    key_l, x_l, w_l, tier_r, imp_l, ladder, caps, block,
                    use_kernel=cfg.use_kernel)
                h_l = dispatch.tier_histogram(tier_r, n_tiers)
                return y_l, jax.lax.psum(h_l, axes)

            spec = P(axes)
            y2, hist = jax.shard_map(
                local, mesh=mesh,
                in_specs=(spec, spec, spec, P(), P()),
                out_specs=(spec, P()), check_vma=False)(
                    x2, tier, imp, key, w)
            return y2, hist

    caps = _caps_for(flat_n, n_tiers, cfg.capacity_fracs)
    tier_routed = dispatch.apply_capacity(tier, imp, caps)
    y2 = dispatch.tiered_mca_matmul(key, x2, w, tier_routed, imp, ladder,
                                    caps, block, use_kernel=cfg.use_kernel)
    return y2, dispatch.tier_histogram(tier_routed, n_tiers)


def merge_stats(stats_list) -> Stats:
    """Aggregate FLOPs accounting across sites/layers."""
    out = {"exact_flops": 0, "mca_flops": 0}
    for s in stats_list:
        out["exact_flops"] = out["exact_flops"] + s["exact_flops"]
        out["mca_flops"] = out["mca_flops"] + s["mca_flops"]
    return out


def flops_reduction(stats: Stats) -> jax.Array:
    """The paper's headline metric: exact / MCA attention-encoding FLOPs."""
    return stats["exact_flops"] / jnp.maximum(stats["mca_flops"], 1)
