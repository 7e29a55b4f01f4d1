"""Pallas TPU kernel: flash attention forward (online softmax) + LSE output.

The LSE (per-row logsumexp) output is what makes MCA cheap to drive: the
attention column-max of Eq. 9 is recoverable from (q, k, lse) in O(n) memory
by the companion kernel in attn_colmax.py — A is never materialized.

Supports GQA natively: kv tensors keep their own head count and the
BlockSpec index_map maps query head h -> kv head h // (Hq // Hkv), so
repeated KV never exists in memory.

The kernel writes LSE as a ``[B, Hq, Sq, 1]`` column (block ``(bq, 1)``):
Mosaic needs a block's last two dims (8, 128)-divisible or equal to the
array's, which a ``(1, bq)`` block of ``[B, Hq, Sq]`` is not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .telemetry import LANE_COUNT, LANE_LAUNCH, lane_inc, tel_shape

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                  *rest, scale, causal, bq, bk, nk, off):
    if len(rest) == 4:                    # telemetry output precedes scratch
        tel_ref, acc_ref, m_ref, l_ref = rest
    else:
        tel_ref = None
        acc_ref, m_ref, l_ref = rest
    bb = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if tel_ref is not None:
        @pl.when((bb == 0) & (h == 0) & (i == 0) & (j == 0))
        def _tel_init():
            tel_ref[...] = lane_inc(LANE_LAUNCH)

    def _compute():
        if tel_ref is not None:
            tel_ref[...] += lane_inc(LANE_COUNT)   # score tiles computed
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)                # [bk, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            # diagonal offset skv - sq: query i sees keys j <= i + off
            # (matches ref_attention's jnp.tril(..., k=skv - sq))
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows + off >= cols, s, NEG_INF)
        m_prev = m_ref[...]                                # [bq, 1]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                     # [bq, 1]
        l_ref[...] = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                # [bk, dh]
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    if causal:
        # skip tiles that are entirely above the (offset) diagonal
        pl.when(j * bk <= i * bq + bq - 1 + off)(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _done():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(safe_l)).astype(lse_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "block_q",
                                             "block_k", "interpret",
                                             "telemetry"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False,
                    telemetry: bool = False):
    """q: [B, Hq, Sq, dh]; k, v: [B, Hkv, Skv, dh]; Hq % Hkv == 0.

    Returns (out [B, Hq, Sq, dh], lse [B, Hq, Sq] float32) — plus a
    ``(1, TEL_WIDTH)`` int32 telemetry buffer (lane 0 = 1 launch, lane 1 =
    score tiles actually computed, i.e. causal skipping excluded) when
    ``telemetry=True``; the telemetry variant runs all-"arbitrary"
    semantics so the shared tile is Megacore-safe.
    """
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    nq, nk = sq // bq, skv // bk

    grid = (b, hq, nq, nk)
    kv_map = lambda bb, h, i, j: (bb, h // group, j, 0)
    out_specs = [
        pl.BlockSpec((1, 1, bq, dh), lambda bb, h, i, j: (bb, h, i, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda bb, h, i, j: (bb, h, i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
    ]
    semantics = ("parallel", "parallel", "parallel", "arbitrary")
    if telemetry:
        out_specs.append(pl.BlockSpec((1, tel_shape().shape[1]),
                                      lambda bb, h, i, j: (0, 0)))
        out_shape.append(tel_shape())
        semantics = ("arbitrary",) * 4
    fn = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, off=skv - sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda bb, h, i, j: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh), kv_map),
            pl.BlockSpec((1, 1, bk, dh), kv_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )
    out, lse, *tel = fn(q, k, v)
    return (out, lse.reshape(b, hq, sq), *tel)
