"""Pallas TPU kernel: Monte-Carlo block-sampled matmul (the MCA hot loop).

Computes   o = sum_k inv_rp[k] * x[:, s[k]*B:(s[k]+1)*B] @ w[s[k]*B:(s[k]+1)*B, :]

The sampled block indices ``s`` live in SMEM via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``): the BlockSpec ``index_map`` of ``x`` and
``w`` reads ``s[k]`` so the DMA engine streams ONLY the sampled blocks
HBM->VMEM.  The gather is folded into the address computation of the
double-buffered pipeline — zero extra cost over a dense matmul of the same
sampled size.  This is the TPU-native analogue of the paper's fused
gather-GEMM CUDA kernel.

Two variants:
  * ``mca_matmul_fixed``  — one sample list for all rows (one tier).
  * ``mca_matmul_ragged`` — per-row-tile sample counts r_tile[i]; compute
    for k >= r_tile[i] is skipped with ``pl.when`` (MXU work saved; the
    prefetch index is clamped so the DMA re-reads the previous block, which
    the pipeline coalesces).

With ``telemetry=True`` both variants return ``(out, tel)`` where ``tel``
is a ``(1, TEL_WIDTH)`` int32 buffer accumulated in-kernel (see
kernels/telemetry.py): lane 0 = 1 launch, lane 1 = sampled block
contributions actually accumulated — ``m_tiles * r`` for fixed,
``sum(r_tile)`` for ragged (the ragged skip makes this device-only
truth).  Telemetry runs with all-"arbitrary" grid semantics so the shared
accumulator tile is Megacore-safe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .telemetry import LANE_COUNT, LANE_LAUNCH, lane_inc, tel_shape

DEFAULT_BLOCK = 128  # sampled column-block width (lane-aligned)


# ---------------------------------------------------------------- fixed R
def _fixed_kernel(s_ref, scale_ref, x_ref, w_ref, o_ref, *rest, n_samples):
    if len(rest) == 2:                    # telemetry output precedes scratch
        tel_ref, acc_ref = rest
    else:
        tel_ref, (acc_ref,) = None, rest
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if tel_ref is not None:
        @pl.when((i == 0) & (j == 0) & (k == 0))
        def _tel_init():
            tel_ref[...] = lane_inc(LANE_LAUNCH)

        @pl.when(j == 0)                  # one count per (row tile, sample)
        def _tel_count():
            tel_ref[...] += lane_inc(LANE_COUNT)

    xb = x_ref[...]                       # [bm, B]
    wb = w_ref[...]                       # [B, bf]
    contrib = jnp.dot(xb, wb, preferred_element_type=jnp.float32)
    acc_ref[...] += scale_ref[k] * contrib

    @pl.when(k == n_samples - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "block_m", "block_f",
                                             "interpret", "telemetry"))
def mca_matmul_fixed(x: jax.Array, w: jax.Array, idx: jax.Array,
                     inv_rp: jax.Array, *, block: int = DEFAULT_BLOCK,
                     block_m: int = 128, block_f: int = 128,
                     interpret: bool = False, telemetry: bool = False):
    """x: [m, d], w: [d, f], idx: [R] int32 block ids, inv_rp: [R] f32.

    Returns ``out`` — or ``(out, tel)`` with ``telemetry=True`` where
    ``tel[0, LANE_COUNT] == m_tiles * r`` (see module docstring).
    """
    m, d = x.shape
    d2, f = w.shape
    assert d == d2 and d % block == 0
    r = idx.shape[0]
    bm = min(block_m, m)
    bf = min(block_f, f)
    assert m % bm == 0 and f % bf == 0, (m, bm, f, bf)

    grid = (m // bm, f // bf, r)
    out_specs = pl.BlockSpec((bm, bf), lambda i, j, k, s, sc: (i, j))
    out_shape = jax.ShapeDtypeStruct((m, f), x.dtype)
    semantics = ("parallel", "parallel", "arbitrary")
    if telemetry:
        out_specs = [out_specs,
                     pl.BlockSpec((1, tel_shape().shape[1]),
                                  lambda i, j, k, s, sc: (0, 0))]
        out_shape = [out_shape, tel_shape()]
        semantics = ("arbitrary", "arbitrary", "arbitrary")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # idx, inv_rp
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, block), lambda i, j, k, s, sc: (i, s[k])),
            pl.BlockSpec((block, bf), lambda i, j, k, s, sc: (s[k], j)),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bm, bf), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_fixed_kernel, n_samples=r),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )
    return fn(idx.astype(jnp.int32), inv_rp.astype(jnp.float32), x, w)


# --------------------------------------------------------------- ragged R
def _ragged_kernel(r_ref, s_ref, scale_ref, x_ref, w_ref, o_ref, *rest,
                   n_samples):
    if len(rest) == 2:
        tel_ref, acc_ref = rest
    else:
        tel_ref, (acc_ref,) = None, rest
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if tel_ref is not None:
        @pl.when((i == 0) & (j == 0) & (k == 0))
        def _tel_init():
            tel_ref[...] = lane_inc(LANE_LAUNCH)

        @pl.when((j == 0) & (k < r_ref[i]))   # only blocks actually used
        def _tel_count():
            tel_ref[...] += lane_inc(LANE_COUNT)

    @pl.when(k < r_ref[i])
    def _accum():
        contrib = jnp.dot(x_ref[...], w_ref[...],
                          preferred_element_type=jnp.float32)
        acc_ref[...] += scale_ref[i, k] * contrib

    @pl.when(k == n_samples - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "block_m", "block_f",
                                             "interpret", "telemetry"))
def mca_matmul_ragged(x: jax.Array, w: jax.Array, r_tile: jax.Array,
                      idx: jax.Array, inv_rp: jax.Array, *,
                      block: int = DEFAULT_BLOCK, block_m: int = 128,
                      block_f: int = 128, interpret: bool = False,
                      telemetry: bool = False):
    """Per-row-tile sample counts.

    x: [m, d]; w: [d, f]; r_tile: [m_tiles] int32 (1..R_max);
    idx: [m_tiles, R_max] block ids; inv_rp: [m_tiles, R_max] f32 weights
    (already contain the 1/(r_i * p) factor; entries past r_tile are unused).
    Returns ``out`` — or ``(out, tel)`` with ``telemetry=True`` where
    ``tel[0, LANE_COUNT] == sum(r_tile)``.
    """
    m, d = x.shape
    _, f = w.shape
    bm = min(block_m, m)
    bf = min(block_f, f)
    assert m % bm == 0 and f % bf == 0
    m_tiles = m // bm
    assert r_tile.shape == (m_tiles,), (r_tile.shape, m_tiles)
    r_max = idx.shape[1]

    grid = (m_tiles, f // bf, r_max)

    def x_map(i, j, k, r, s, sc):
        kk = jnp.minimum(k, r[i] - 1)     # clamp: re-read last needed block
        return (i, s[i, kk])

    def w_map(i, j, k, r, s, sc):
        kk = jnp.minimum(k, r[i] - 1)
        return (s[i, kk], j)

    out_specs = pl.BlockSpec((bm, bf), lambda i, j, k, r, s, sc: (i, j))
    out_shape = jax.ShapeDtypeStruct((m, f), x.dtype)
    semantics = ("parallel", "parallel", "arbitrary")
    if telemetry:
        out_specs = [out_specs,
                     pl.BlockSpec((1, tel_shape().shape[1]),
                                  lambda i, j, k, r, s, sc: (0, 0))]
        out_shape = [out_shape, tel_shape()]
        semantics = ("arbitrary", "arbitrary", "arbitrary")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # r_tile, idx, inv_rp
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, block), x_map),
            pl.BlockSpec((block, bf), w_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bm, bf), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_ragged_kernel, n_samples=r_max),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )
    return fn(r_tile.astype(jnp.int32), idx.astype(jnp.int32),
              inv_rp.astype(jnp.float32), x, w)
