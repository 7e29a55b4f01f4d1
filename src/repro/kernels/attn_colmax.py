"""Pallas TPU kernel: attention column-max from (q, k, lse) in O(n) memory.

colmax[j] = max_i A[i, j] = max_i exp(q_i . k_j * scale - lse_i)

This is the r-schedule driver of MCA (Eq. 9).  Materializing A to take a
column max would cost O(n^2) memory and defeat flash attention; instead we
recompute score tiles (like a flash backward pass does) and fold the max.
Output is per query-head; the ops wrapper reduces over heads.

Block layouts follow Mosaic's rule that a block's last two dims be
(8, 128)-divisible or equal to the array's: LSE enters as a
``[B, Hq, Sq, 1]`` column (block ``(bq, 1)``) and the column max leaves
as a ``[B, Hq, 1, Skv]`` row (block ``(1, bk)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .telemetry import LANE_COUNT, LANE_LAUNCH, lane_inc, tel_shape


def _colmax_kernel(q_ref, k_ref, lse_ref, o_ref, *rest,
                   scale, causal, bq, bk, nq, off):
    if len(rest) == 2:                    # telemetry output precedes scratch
        tel_ref, cm_ref = rest
    else:
        tel_ref, (cm_ref,) = None, rest
    bb = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)   # kv tile
    i = pl.program_id(3)   # q tile (innermost)

    @pl.when(i == 0)
    def _init():
        cm_ref[...] = jnp.zeros_like(cm_ref)

    if tel_ref is not None:
        @pl.when((bb == 0) & (h == 0) & (j == 0) & (i == 0))
        def _tel_init():
            tel_ref[...] = lane_inc(LANE_LAUNCH)

    def _compute():
        if tel_ref is not None:
            tel_ref[...] += lane_inc(LANE_COUNT)   # score tiles recomputed
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        lse = lse_ref[0, 0]                                  # [bq, 1]
        a = jnp.exp(s - lse)                                 # [bq, bk]
        if causal:
            # diagonal offset skv - sq, as in ref_colmax's tril(k=skv - sq)
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            a = jnp.where(rows + off >= cols, a, 0.0)
        cm_ref[...] = jnp.maximum(cm_ref[...],
                                  jnp.max(a, axis=0, keepdims=True))

    if causal:
        # q tiles strictly above the (offset) kv tile see nothing of it
        pl.when(i * bq + bq - 1 + off >= j * bk)(_compute)
    else:
        _compute()

    @pl.when(i == nq - 1)
    def _done():
        o_ref[0, 0] = cm_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "block_q",
                                             "block_k", "interpret",
                                             "telemetry"))
def attn_colmax(q: jax.Array, k: jax.Array, lse: jax.Array, *, scale: float,
                causal: bool = True, block_q: int = 128, block_k: int = 128,
                interpret: bool = False, telemetry: bool = False):
    """q: [B, Hq, Sq, dh]; k: [B, Hkv, Skv, dh]; lse: [B, Hq, Sq] (from
    flash_attention).  Returns colmax [B, Hq, Skv] float32 — or
    ``(colmax, tel)`` with ``telemetry=True`` (lane 0 = 1 launch, lane 1 =
    score tiles recomputed; all-"arbitrary" semantics, see
    kernels/telemetry.py).
    """
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0
    nq, nk = sq // bq, skv // bk

    grid = (b, hq, nk, nq)
    out_specs = pl.BlockSpec((1, 1, 1, bk),
                             lambda bb, h, j, i: (bb, h, 0, j))
    out_shape = jax.ShapeDtypeStruct((b, hq, 1, skv), jnp.float32)
    semantics = ("parallel", "parallel", "parallel", "arbitrary")
    if telemetry:
        out_specs = [out_specs,
                     pl.BlockSpec((1, tel_shape().shape[1]),
                                  lambda bb, h, j, i: (0, 0))]
        out_shape = [out_shape, tel_shape()]
        semantics = ("arbitrary",) * 4
    fn = pl.pallas_call(
        functools.partial(_colmax_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, off=skv - sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda bb, h, j, i: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda bb, h, j, i: (bb, h // group, j, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bb, h, j, i: (bb, h, i, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((1, bk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )
    out = fn(q, k, lse.reshape(b, hq, sq, 1))
    if telemetry:
        return out[0].reshape(b, hq, skv), out[1]
    return out.reshape(b, hq, skv)
