"""Pallas TPU kernel: slot-sliced KV-cache update for per-slot decoding.

Writes one new KV row per batch row at a *per-row* cache position::

    cache[b, pos[b]] = new[b, 0]          for every b

This is the decode-side primitive of per-slot continuous batching: every
decode slot advances at its own sequence position, so the classic
``dynamic_update_slice`` (one shared position for the whole batch) no
longer applies.  A naive ``cache.at[arange(B), pos].set(...)`` lowers to a
general scatter; this kernel instead issues one DMA per batch row, from
the new row straight into ``cache[b, pos[b]]`` in HBM, so ONLY the B
touched rows are written — the untouched cache slots are never read or
copied (``input_output_aliases`` makes the donated cache buffer the
output buffer).

Every operand stays in HBM (``memory_space=ANY``) in the caller's own
shape and layout (``[B, S, ...]``, no reshape); the kernel body starts the
B row copies, then waits for them.  A blocked write would need a one-row
block, which Mosaic refuses (a block's last two dims must be
(8, 128)-divisible or equal to the array's), and any reshaped view that it
accepts is tiled differently from the cache, so XLA would copy the whole
cache into that layout and back around the kernel on every call.

A row DMA compiles only when a row is whole tiles of the cache's layout,
which XLA tiles over its two minor dims: see :func:`row_dma_ok`.  The
``[B, S, kv_heads, head_dim]`` GQA cache qualifies; a ``[B, S, F]`` cache,
whose rows split a tile, does not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .telemetry import LANE_COUNT, LANE_LAUNCH, lane_inc, tel_shape


def row_dma_ok(shape, dtype) -> bool:
    """Whether rows ``[*shape[2:]]`` of a ``shape`` cache are whole tiles.

    The last dim must fill 128-lane tiles.  The second-minor dim ``n`` must
    fill its sublane tile: a dtype narrower than 32 bits packs ``pack``
    rows per sublane, and XLA tiles a small ``n`` to the next power of two,
    so ``n`` must be a power of two of at least ``pack`` or a multiple of
    ``8 * pack``.
    """
    if len(shape) < 4 or shape[-1] % 128:
        return False
    pack = max(1, 4 // jnp.dtype(dtype).itemsize)
    n = shape[-2]
    return n % (8 * pack) == 0 or (n >= pack and n & (n - 1) == 0)


def _kernel(pos_ref, new_ref, cache_ref, out_ref, *rest):
    del cache_ref                   # aliased to out_ref
    *tel, sems = rest
    b = new_ref.shape[0]

    def row_copy(i):
        return pltpu.make_async_copy(
            new_ref.at[pl.ds(i, 1)],
            out_ref.at[pl.ds(i, 1), pl.ds(pos_ref[i], 1)], sems.at[i])

    for i in range(b):
        row_copy(i).start()
    for i in range(b):
        row_copy(i).wait()
    if tel:
        (tel_ref,) = tel
        tel_ref[...] = lane_inc(LANE_LAUNCH) + b * lane_inc(LANE_COUNT)


@functools.partial(jax.jit, static_argnames=("interpret", "telemetry"))
def kv_slot_update(cache: jax.Array, new: jax.Array, pos: jax.Array,
                   *, interpret: bool = False, telemetry: bool = False):
    """cache: [B, S, ...]; new: [B, 1, ...] (same trailing dims); pos: [B]
    int32 -> updated cache.

    Callers must pass positions in [0, S) (the serve engine's admission
    control guarantees it): the row DMAs are not bounds-checked.  On TPU
    the cache's shape must pass :func:`row_dma_ok`.

    With ``telemetry=True`` returns ``(cache, tel)`` where the
    ``(1, TEL_WIDTH)`` int32 buffer holds lane 0 = 1 launch, lane 1 = B
    rows written.
    """
    b = cache.shape[0]
    assert new.shape == (b, 1) + cache.shape[2:], (new.shape, cache.shape)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out_specs = hbm
    out_shape = jax.ShapeDtypeStruct(cache.shape, cache.dtype)
    if telemetry:
        out_specs = [out_specs, pl.BlockSpec(memory_space=pltpu.VMEM)]
        out_shape = [out_shape, tel_shape()]
    fn = pl.pallas_call(
        _kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), hbm, hbm],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.SemaphoreType.DMA((b,))],
        input_output_aliases={2: 0},                 # cache buffer -> out
        interpret=interpret,
    )
    return fn(pos.astype(jnp.int32), new, cache)
