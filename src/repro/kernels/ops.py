"""Jit'd public wrappers around the Pallas kernels.

On the CPU backend the kernels run in ``interpret=True`` mode (the kernel
body executes as jnp), so the whole framework is testable offline; on
every other backend they compile, so a backend the kernels cannot target
fails loudly instead of silently interpreting.

Two layers of accounting, deliberately distinct (see ROADMAP § Observability):

* **dispatch-time** — kernel-vs-fallback decisions are made here on static
  shapes and recorded as ``kernels.<op>.kernel_calls`` /
  ``kernels.<op>.fallback_calls``.  Under ``jax.jit`` this Python runs
  once per compilation, so these count *distinct traced call sites* (how
  many places in the program dispatched which path), not executions.
* **device launches** — when ``obs.devtel`` is enabled, each wrapper also
  emits per-*execution* counts: ``kernels.<op>.device_launches`` fires
  once every time the op actually runs on the device (every ``lax.scan``
  iteration of a decode burst, every call of a compiled function), plus a
  per-op work count (``device_sampled_blocks`` for the MCA matmuls —
  sampled block contributions accumulated in-kernel, so the ragged
  kernel's skipped samples are excluded; ``device_tiles`` for
  flash/colmax score tiles; ``device_rows_written`` for the KV update).
  On the kernel path the counts come from an in-kernel telemetry buffer
  (kernels/telemetry.py); on the fallback path the wrapper emits the
  analytically equivalent values, so both paths report launches the same
  way.  Telemetry is a trace-time flag: enable it *before* the first
  compilation of the code under measurement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.obs import devtel

from . import attn_colmax as _colmax_mod
from . import cache_update as _cache_mod
from . import flash_attention as _flash_mod
from . import mca_matmul as _mca_mod
from . import ref as _ref
from .telemetry import LANE_COUNT, LANE_LAUNCH


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _count(op: str, used_kernel: bool) -> None:
    which = "kernel_calls" if used_kernel else "fallback_calls"
    obs.get_registry().counter(f"kernels.{op}.{which}").inc()


def _emit_tel(op: str, work_metric: str, launches, work) -> None:
    """Per-execution device telemetry for one op (no-op when disabled)."""
    devtel.emit_vec(
        (f"kernels.{op}.device_launches", f"kernels.{op}.{work_metric}"),
        (launches, work))


def mca_matmul(x: jax.Array, w: jax.Array, idx: jax.Array, inv_rp: jax.Array,
               *, block: int = 128, block_m: int = 128, block_f: int = 128
               ) -> jax.Array:
    """Fixed-R Monte-Carlo block-sampled matmul (one precision tier).

    Device telemetry: ``device_sampled_blocks`` counts one per
    (row tile, sample) — ``m_tiles * R`` on the kernel path; the dense
    fallback has no row tiling, so it counts the sample-list length ``R``.
    """
    m, d = x.shape
    f = w.shape[1]
    bm, bf = min(block_m, m), min(block_f, f)
    use_kernel = m % bm == 0 and d % block == 0 and f % bf == 0
    _count("mca_matmul", use_kernel)
    if not use_kernel:
        out = _ref.ref_mca_matmul_fixed(x, w, idx, inv_rp, block)
        _emit_tel("mca_matmul", "device_sampled_blocks", 1, idx.shape[0])
        return out
    with obs.trace("mca_matmul"):
        if devtel.enabled():
            out, tel = _mca_mod.mca_matmul_fixed(
                x, w, idx, inv_rp, block=block, block_m=bm, block_f=bf,
                interpret=_interpret(), telemetry=True)
            _emit_tel("mca_matmul", "device_sampled_blocks",
                      tel[0, LANE_LAUNCH], tel[0, LANE_COUNT])
            return out
        return _mca_mod.mca_matmul_fixed(
            x, w, idx, inv_rp, block=block, block_m=bm, block_f=bf,
            interpret=_interpret())


def _ragged_fallback(x, w, r_tile, idx, inv_rp, block, bm):
    """Traceable oracle for the ragged kernel (masked dense gather-GEMM).

    Unlike ref.ref_mca_matmul_ragged this never concretizes r_tile, so it
    is safe inside jit; samples past r_tile[t] are masked to zero weight.
    """
    m, d = x.shape
    f = w.shape[1]
    nb = d // block
    m_tiles, r_max = idx.shape
    xb = x.reshape(m_tiles, bm, nb, block)
    wb = w.reshape(nb, block, f)
    live = jnp.arange(r_max)[None, :] < r_tile[:, None]        # [T, R]
    wgt = jnp.where(live, inv_rp.astype(jnp.float32), 0.0)
    xg = jnp.take_along_axis(xb, idx[:, None, :, None], axis=2)  # [T,bm,R,B]
    wg = wb[idx]                                                 # [T,R,B,f]
    out = jnp.einsum("tmrb,trbf,tr->tmf", xg.astype(jnp.float32),
                     wg.astype(jnp.float32), wgt)
    return out.reshape(m, f).astype(x.dtype)


def mca_matmul_ragged(x, w, r_tile, idx, inv_rp, *, block=128,
                      block_m=128, block_f=128):
    """Per-row-tile-R Monte-Carlo matmul (sorted/ragged precision).

    The row-tile size is pinned by ``r_tile``'s length: the kernel needs
    ``min(block_m, m)`` row tiles to line up with it, otherwise we fall
    back to the dense masked oracle with ``bm = m // len(r_tile)``.

    Device telemetry: ``device_sampled_blocks == sum(r_tile)`` on both
    paths (blocks the ragged kernel actually accumulated — its
    ``pl.when`` skipping makes this the device-only truth the dispatcher
    cannot see).
    """
    m, d = x.shape
    f = w.shape[1]
    m_tiles = r_tile.shape[0]
    assert m % m_tiles == 0, (m, m_tiles)
    bm, bf = min(block_m, m), min(block_f, f)
    use_kernel = (m % bm == 0 and m // bm == m_tiles
                  and d % block == 0 and f % bf == 0)
    _count("mca_matmul_ragged", use_kernel)
    if not use_kernel:
        out = _ragged_fallback(x, w, r_tile, idx, inv_rp, block,
                               m // m_tiles)
        _emit_tel("mca_matmul_ragged", "device_sampled_blocks",
                  1, jnp.sum(r_tile))
        return out
    with obs.trace("mca_matmul_ragged"):
        if devtel.enabled():
            out, tel = _mca_mod.mca_matmul_ragged(
                x, w, r_tile, idx, inv_rp, block=block, block_m=bm,
                block_f=bf, interpret=_interpret(), telemetry=True)
            _emit_tel("mca_matmul_ragged", "device_sampled_blocks",
                      tel[0, LANE_LAUNCH], tel[0, LANE_COUNT])
            return out
        return _mca_mod.mca_matmul_ragged(
            x, w, r_tile, idx, inv_rp, block=block, block_m=bm,
            block_f=bf, interpret=_interpret())


def kv_slot_update(cache: jax.Array, new: jax.Array, pos: jax.Array
                   ) -> jax.Array:
    """Per-row KV-cache write: ``cache[b, pos[b]] = new[b, 0]``.

    cache: [B, S, ...]; new: [B, 1, ...] (same trailing dims); pos: [B]
    int32.  The Pallas kernel DMAs each new row to ``cache[b, pos[b]]``
    (only the B touched rows are written, in place through
    ``input_output_aliases``, with no reshape of the cache); when a row
    is not whole tiles of the cache's layout (``cache_update.row_dma_ok``)
    the XLA scatter fallback runs instead.

    Device telemetry: ``device_rows_written == B`` per execution on both
    paths — a K-step decode burst therefore shows K launches where the
    dispatch counter shows one traced call site.
    """
    b = cache.shape[0]
    use_kernel = _cache_mod.row_dma_ok(cache.shape, cache.dtype)
    _count("kv_slot_update", use_kernel)
    if not use_kernel:
        out = cache.at[jnp.arange(b), pos].set(new[:, 0])
        _emit_tel("kv_slot_update", "device_rows_written", 1, b)
        return out
    with obs.trace("kv_slot_update"):
        if devtel.enabled():
            out, tel = _cache_mod.kv_slot_update(
                cache, new, pos, interpret=_interpret(), telemetry=True)
            _emit_tel("kv_slot_update", "device_rows_written",
                      tel[0, LANE_LAUNCH], tel[0, LANE_COUNT])
        else:
            out = _cache_mod.kv_slot_update(cache, new, pos,
                                            interpret=_interpret())
    return out


def flash_attention(q, k, v, *, scale, causal=True, block_q=128, block_k=128):
    """Flash attention fwd; returns (out, lse).

    Device telemetry: ``device_tiles`` counts score tiles actually
    computed in-kernel (causally skipped tiles excluded); the dense
    fallback reports 0 tiles (no tiling), launches still count 1 per
    execution.
    """
    sq, skv = q.shape[2], k.shape[2]
    bq, bk = min(block_q, sq), min(block_k, skv)
    use_kernel = sq % bq == 0 and skv % bk == 0
    _count("flash_attention", use_kernel)
    if not use_kernel:
        out = _ref.ref_attention(q, k, v, scale=scale, causal=causal)
        _emit_tel("flash_attention", "device_tiles", 1, 0)
        return out
    with obs.trace("flash_attention"):
        if devtel.enabled():
            out, lse, tel = _flash_mod.flash_attention(
                q, k, v, scale=scale, causal=causal, block_q=bq,
                block_k=bk, interpret=_interpret(), telemetry=True)
            _emit_tel("flash_attention", "device_tiles",
                      tel[0, LANE_LAUNCH], tel[0, LANE_COUNT])
            return out, lse
        return _flash_mod.flash_attention(
            q, k, v, scale=scale, causal=causal, block_q=bq,
            block_k=bk, interpret=_interpret())


def attn_colmax(q, k, lse, *, scale, causal=True, block_q=128, block_k=128,
                reduce_heads=True):
    """Column max of A from (q, k, lse); optionally reduced over heads.

    Device telemetry mirrors flash_attention: ``device_tiles`` = score
    tiles recomputed in-kernel, fallback reports (1 launch, 0 tiles).
    """
    sq, skv = q.shape[2], k.shape[2]
    bq, bk = min(block_q, sq), min(block_k, skv)
    use_kernel = sq % bq == 0 and skv % bk == 0
    _count("attn_colmax", use_kernel)
    if not use_kernel:
        cm = _ref.ref_colmax(q, k, lse, scale=scale, causal=causal)
        _emit_tel("attn_colmax", "device_tiles", 1, 0)
    else:
        with obs.trace("attn_colmax"):
            if devtel.enabled():
                cm, tel = _colmax_mod.attn_colmax(
                    q, k, lse, scale=scale, causal=causal, block_q=bq,
                    block_k=bk, interpret=_interpret(), telemetry=True)
                _emit_tel("attn_colmax", "device_tiles",
                          tel[0, LANE_LAUNCH], tel[0, LANE_COUNT])
            else:
                cm = _colmax_mod.attn_colmax(
                    q, k, lse, scale=scale, causal=causal, block_q=bq,
                    block_k=bk, interpret=_interpret())
    if reduce_heads:
        cm = jnp.max(cm, axis=1)        # [B, Skv]
    return cm
