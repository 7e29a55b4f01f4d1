"""Pallas TPU kernel: flash attention forward (online softmax), with an
optional LSE output.

One kernel body serves two layouts:

* ``flash_attention`` — head-major ``q [B, Hq, Sq, dh]``, ``k, v [B, Hkv,
  Skv, dh]``, returning ``(out, lse)``.  The LSE (per-row logsumexp) is
  what makes MCA cheap to drive: the attention column-max of Eq. 9 is
  recoverable from (q, k, lse) in O(n) memory by the companion kernel in
  attn_colmax.py — A is never materialized.
* ``flash_prefill`` — the causal self-attention of a cache-filling
  prefill, in the layout the projections produce: ``q [B, S, Hq * dh]``,
  ``k, v [B, S, Hkv * dh]``, with per-row left-padding offsets.  It
  writes no LSE.

Both read GQA natively: one grid program holds all ``g = Hq // Hkv``
query heads of a kv head against one K/V tile, so each K/V tile is read
once per kv head, and repeated KV never exists in memory.  Operands stay
in their own dtype (bf16 dots accumulate in f32 on the MXU); scores and
the running sums are f32, and the probabilities meet V in V's dtype.

Tiles are skipped where no query of the tile may attend any key of it:
above the causal diagonal, or wholly inside a row's left padding (the
offsets are a scalar-prefetch operand).  A skipped tile costs no DMA
either: the K/V index map clamps to the range of tiles a query tile
needs, so the pipeline finds the block it already holds.  Keys below a
row's offset are masked; query rows with no key to attend (padding rows)
come out as zeros.  Only tiles that straddle the diagonal or the padding
edge build a mask.

The running max and sum of head ``h`` live in lane ``h`` of a ``[bq,
128]`` scratch each, so g heads cost one tile of scratch, not g.  The
head-major kernel writes LSE as a ``[B, Hq, Sq, 1]`` column (block
``(g, bq, 1)``): Mosaic needs a block's last two dims (8, 128)-divisible
or equal to the array's, which a ``(1, bq)`` block of ``[B, Hq, Sq]`` is
not.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .telemetry import LANE_COUNT, LANE_LAUNCH, lane_inc, tel_shape

NEG_INF = -1e30
_LANES = 128
_SQUEEZE = pl.squeezed
# g heads of q, out and the f32 accumulator per grid step outgrow the
# default 16 MiB of scoped VMEM past 512 x 512 tiles (a v5e core has 128)
_VMEM_LIMIT = 64 << 20
# causal prefill tiles, largest first (query rows, key rows)
PREFILL_BLOCKS = ((512, 512), (256, 256), (128, 128))


def prefill_blocks(s: int, dh: int):
    """``(block_q, block_k)`` for a causal prefill of ``s`` positions with
    head dim ``dh``, or None where the kernel does not apply: the head dim
    must be whole lanes and ``s`` whole tiles of at least 128 rows."""
    if dh % _LANES:
        return None
    for bq, bk in PREFILL_BLOCKS:
        if s % bq == 0 and s % bk == 0:
            return bq, bk
    return None


def _needed(i, j, off, *, bq, bk, causal, diag):
    """Whether tile (i, j) holds a (query, key) pair that may attend:
    below the diagonal, and past the row's padding on both sides."""
    ok = (j * bk + bk > off) & (i * bq + bq > off)
    if causal:
        ok &= j * bk <= i * bq + bq - 1 + diag
    return ok


def _kv_block(i, j, off, *, bq, bk, nk, causal, diag):
    """The K/V tile grid step (i, j) reads: ``j`` clamped to the tiles
    query tile ``i`` needs, so a skipped step re-uses the resident one."""
    hi = jnp.clip((i * bq + bq - 1 + diag) // bk, 0, nk - 1) if causal \
        else nk - 1
    return jnp.minimum(jnp.maximum(j, off // bk), hi)


def _flash_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, *rest, scale, causal,
                  g, dh, bq, bk, nk, diag, head_major, with_lse, telemetry):
    rest = list(rest)
    lse_ref = rest.pop(0) if with_lse else None
    tel_ref = rest.pop(0) if telemetry else None
    acc_ref, m_ref, l_ref = rest
    bb, h, i, j = (pl.program_id(a) for a in range(4))
    off = off_ref[bb]

    def head(ref, hh):
        """Head ``hh``'s ``[rows, dh]`` view of a q, out or acc block."""
        return (ref.at[hh] if head_major
                else ref.at[:, pl.ds(hh * dh, dh)])

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if tel_ref is not None:
        @pl.when((bb == 0) & (h == 0) & (i == 0) & (j == 0))
        def _tel_init():
            tel_ref[...] = lane_inc(LANE_LAUNCH)

    def _compute(masked: bool):
        if tel_ref is not None:
            tel_ref[...] += g * lane_inc(LANE_COUNT)   # score tiles, per head
        k = k_ref[...]
        v = v_ref[...]
        if masked:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            # diagonal offset skv - sq: query r sees keys c <= r + diag
            # (ref_attention's jnp.tril(..., k=skv - sq)), none below off
            mask = cols >= off
            if causal:
                mask &= rows + diag >= cols
        for hh in range(g):
            s = jax.lax.dot_general(head(q_ref, hh)[...], k,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if masked:
                # -inf, not NEG_INF: a row with no key yet keeps p = 0
                s = jnp.where(mask, s, -jnp.inf)
            m_prev = m_ref[:, hh:hh + 1]                    # [bq, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[:, hh:hh + 1] = (corr * l_ref[:, hh:hh + 1]
                                   + jnp.sum(p, axis=-1, keepdims=True))
            m_ref[:, hh:hh + 1] = m_new
            acc = head(acc_ref, hh)
            acc[...] = acc[...] * corr + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    needed = _needed(i, j, off, bq=bq, bk=bk, causal=causal, diag=diag)
    edge = j * bk < off
    if causal:
        edge |= j * bk + bk - 1 > i * bq + diag
    pl.when(needed & edge)(lambda: _compute(True))
    pl.when(needed & ~edge)(lambda: _compute(False))

    @pl.when(j == nk - 1)
    def _done():
        for hh in range(g):
            l = l_ref[:, hh:hh + 1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            head(o_ref, hh)[...] = (head(acc_ref, hh)[...] / safe_l
                                    ).astype(o_ref.dtype)
            if lse_ref is not None:
                lse_ref[hh] = (m_ref[:, hh:hh + 1] + jnp.log(safe_l)
                               ).astype(lse_ref.dtype)


def _call(q, k, v, off, *, g, dh, sq, skv, scale, causal, bq, bk,
          head_major, with_lse, interpret, telemetry):
    """The pallas_call over grid (batch, kv head, query tile, key tile)."""
    b = k.shape[0]
    hkv = k.shape[1] if head_major else k.shape[2] // dh
    assert g <= _LANES, g
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    nq, nk = sq // bq, skv // bk
    diag = skv - sq
    kv_at = functools.partial(_kv_block, bq=bq, bk=bk, nk=nk, causal=causal,
                              diag=diag)
    if head_major:
        q_spec = pl.BlockSpec((_SQUEEZE, g, bq, dh),
                              lambda bb, h, i, j, off: (bb, h, i, 0))
        kv_spec = pl.BlockSpec(
            (_SQUEEZE, _SQUEEZE, bk, dh),
            lambda bb, h, i, j, off: (bb, h, kv_at(i, j, off[bb]), 0))
        acc_shape = (g, bq, dh)
    else:
        q_spec = pl.BlockSpec((_SQUEEZE, bq, g * dh),
                              lambda bb, h, i, j, off: (bb, i, h))
        kv_spec = pl.BlockSpec(
            (_SQUEEZE, bk, dh),
            lambda bb, h, i, j, off: (bb, kv_at(i, j, off[bb]), h))
        acc_shape = (bq, g * dh)
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((_SQUEEZE, g, bq, 1),
                                      lambda bb, h, i, j, off: (bb, h, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, hkv * g, sq, 1),
                                              jnp.float32))
    semantics = ("parallel", "parallel", "parallel", "arbitrary")
    if telemetry:
        out_specs.append(pl.BlockSpec((1, tel_shape().shape[1]),
                                      lambda bb, h, i, j, off: (0, 0)))
        out_shape.append(tel_shape())
        semantics = ("arbitrary",) * 4
    fn = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal, g=g,
                          dh=dh, bq=bq, bk=bk, nk=nk, diag=diag,
                          head_major=head_major, with_lse=with_lse,
                          telemetry=telemetry),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32),
                            pltpu.VMEM((bq, _LANES), jnp.float32),
                            pltpu.VMEM((bq, _LANES), jnp.float32)],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )
    return fn(off.astype(jnp.int32), q, k, v)


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
    score tiles actually computed per query head, i.e. causal skipping
    excluded) when ``telemetry=True``; the telemetry variant runs
    all-"arbitrary" semantics so the shared tile is Megacore-safe.
    """
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    out, lse, *tel = _call(
        q, k, v, jnp.zeros((b,), jnp.int32), g=hq // hkv, dh=dh, sq=sq,
        skv=skv, scale=scale, causal=causal, bq=min(block_q, sq),
        bk=min(block_k, skv), head_major=True, with_lse=True,
        interpret=interpret, telemetry=telemetry)
    return (out, lse.reshape(b, hq, sq), *tel)


@functools.partial(jax.jit, static_argnames=("n_kv_heads", "scale",
                                             "block_q", "block_k",
                                             "interpret", "telemetry"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                  pos_offset: Optional[jax.Array], *, n_kv_heads: int,
                  scale: float, block_q: int, block_k: int,
                  interpret: bool = False, telemetry: bool = False):
    """Causal self-attention of a prefill, in the projections' layout.

    q: [B, S, Hq * dh]; k, v: [B, S, Hkv * dh] (head h in columns
    ``h * dh : (h + 1) * dh``); pos_offset: [B] int32 left-padding counts
    (keys below a row's offset are masked, query rows below it come out
    zero), or None for none.  Returns out [B, S, Hq * dh] in q's dtype —
    plus the telemetry buffer when ``telemetry=True``.
    """
    b, s, width = q.shape
    dh = k.shape[2] // n_kv_heads
    off = (jnp.zeros((b,), jnp.int32) if pos_offset is None
           else pos_offset)
    out, *tel = _call(
        q, k, v, off, g=width // (n_kv_heads * dh), dh=dh, sq=s, skv=s,
        scale=scale, causal=True, bq=block_q, bk=block_k, head_major=False,
        with_lse=False, interpret=interpret, telemetry=telemetry)
    return (out, *tel) if telemetry else out
