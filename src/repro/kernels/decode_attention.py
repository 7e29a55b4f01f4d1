"""Pallas TPU kernel: one-token decode attention over a layer-stacked cache.

The decode step carries the whole ``[L, B, slots, hkv, dh]`` K/V stack
through its layer scan; this kernel reads layer ``layer`` of it straight
from HBM (the layer index is a scalar-prefetch operand of the BlockSpec's
index map), so no per-layer slice of the cache is ever cut out, relaid
out or staged by XLA.

The stack is read as it lies, viewed ``[L, B, N, dh]`` with ``N = slots *
hkv`` (the kv heads folded into the rows: a bitcast of the cache's tiled
layout).  Every query head meets every cached row of its batch row and
the rows of other kv heads are masked out of its softmax: reading per kv
head would need the cache relaid out ``[B, hkv, slots, dh]`` first, a
copy of the layer's K and V on every step, which costs more than the
hkv-fold score FLOPs this spends instead (decode attention stays bound
by the bytes it reads).

The current token's own score ``s1`` and value ``v1`` seed the online
softmax, so the query attends to the cache as it was before this step's
write plus itself; the caller writes the new row afterwards.  Scores and
the running sums are f32; probabilities meet V in the cache's dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_BLOCK_BYTES = 2 << 20          # of K per grid step (V the same)
_MAX_ROWS = 8                   # batch rows per grid step (unrolled)


def pick_blocks(b: int, n: int, dh: int, itemsize: int):
    """``(batch rows, cache rows)`` per grid step, or None when no block
    fits: a block's rows must be whole 128-lane tiles of the validity mask
    (or all ``n`` of them) and hold at most ``_BLOCK_BYTES`` of K."""
    if dh % 128:
        return None
    row_bytes = dh * itemsize
    if n * row_bytes <= _BLOCK_BYTES:
        bn = n
    else:
        fits = [d for d in range(128, n, 128)
                if n % d == 0 and d * row_bytes <= _BLOCK_BYTES]
        if not fits:
            return None
        bn = max(fits)
    bb = max(d for d in range(1, min(b, _MAX_ROWS) + 1)
             if b % d == 0 and (d == 1 or d * bn * row_bytes <= _BLOCK_BYTES))
    return bb, bn


def _kernel(layer_ref, q_ref, k_ref, v_ref, valid_ref, s1_ref, v1_ref,
            o_ref, l_ref, m_sc, l_sc, acc_sc, *, scale, g, hkv, nj):
    del layer_ref                               # used by the index maps
    j = pl.program_id(1)
    bb, r, _ = q_ref.shape
    bn = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = s1_ref[...]
        l_sc[...] = jnp.ones_like(l_sc)
        acc_sc[...] = v1_ref[...].astype(jnp.float32)

    head_of_row = jax.lax.broadcasted_iota(jnp.int32, (r, bn), 0) // g
    head_of_col = jax.lax.broadcasted_iota(jnp.int32, (r, bn), 1) % hkv
    own = head_of_row == head_of_col
    for i in range(bb):
        s = jax.lax.dot_general(q_ref[i], k_ref[0, i],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(own & (valid_ref[i] != 0), s, NEG_INF)    # [r, bn]
        m_prev = m_sc[i]                                        # [r, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[i] = corr * l_sc[i] + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[i] = acc_sc[i] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, i],
            preferred_element_type=jnp.float32)
        m_sc[i] = m_new

    @pl.when(j == nj - 1)
    def _done():
        o_ref[...] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)
        l_ref[...] = l_sc[...]


@functools.partial(jax.jit, static_argnames=("scale", "hkv", "block_b",
                                             "block_n", "interpret"))
def decode_attention(q, k, v, valid, s1, v1, layer, *, scale: float,
                     hkv: int, block_b: int, block_n: int,
                     interpret: bool = False):
    """q: [B, R, dh], the R = hkv * g query heads kv-head-major; k, v:
    [L, B, N, dh], a layer-stacked [L, B, slots, hkv, dh] cache with its
    kv heads folded into the rows (row n holds kv head n % hkv); valid:
    [B, 1, N] int32, nonzero where a row may be attended; s1: [B, R, 1]
    f32, the current token's scaled score per query head; v1: [B, R, dh],
    its value under each query head; layer: scalar int32.

    Returns (out [B, R, dh] in q's dtype, l [B, R, 1] f32): the softmax
    denominators relative to each head's largest score, so a head's
    largest attention probability is 1 / l.
    """
    b, r, dh = q.shape
    n = k.shape[2]
    assert r % hkv == 0 and n % hkv == 0, (r, n, hkv)
    assert b % block_b == 0 and n % block_n == 0, (b, block_b, n, block_n)
    nj = n // block_n
    if not interpret:
        # the stack stays in HBM: left free, XLA may stage a small one in
        # VMEM and copy the whole of it in on every layer
        k = pltpu.with_memory_space_constraint(k, pltpu.HBM)
        v = pltpu.with_memory_space_constraint(v, pltpu.HBM)
    rows = lambda i, j, layer: (i, 0, 0)                      # noqa: E731
    cache = pl.BlockSpec((1, block_b, block_n, dh),
                         lambda i, j, layer: (layer[0], i, j, 0))
    fn = pl.pallas_call(
        functools.partial(_kernel, scale=scale, g=r // hkv, hkv=hkv, nj=nj),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // block_b, nj),
            in_specs=[
                pl.BlockSpec((block_b, r, dh), rows),
                cache,
                cache,
                pl.BlockSpec((block_b, 1, block_n),
                             lambda i, j, layer: (i, 0, j)),
                pl.BlockSpec((block_b, r, 1), rows),
                pl.BlockSpec((block_b, r, dh), rows),
            ],
            out_specs=[pl.BlockSpec((block_b, r, dh), rows),
                       pl.BlockSpec((block_b, r, 1), rows)],
            scratch_shapes=[pltpu.VMEM((block_b, r, 1), jnp.float32),
                            pltpu.VMEM((block_b, r, 1), jnp.float32),
                            pltpu.VMEM((block_b, r, dh), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, r, dh), q.dtype),
                   jax.ShapeDtypeStruct((b, r, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(jnp.reshape(layer, (1,)).astype(jnp.int32), q, k, v,
              valid.astype(jnp.int32), s1.astype(jnp.float32), v1)
