"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel (or the serve decode burst) at
StarCoder2-3B widths for one chip of a ``v5e:2x2`` topology that is
described, not attached, and asserts the compiled HLO holds the Mosaic
kernel (``tpu_custom_call``).  This catches block-layout, tiling and VMEM
refusals that interpret mode cannot see.  The KV write is also held to
its purpose: no copy of the cache around the kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so each pytest worker that
collects this file must not touch it.  Keep these tests in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops as kernel_ops
from repro.kernels import cache_update
from repro.kernels.attn_colmax import attn_colmax
from repro.kernels.cache_update import kv_slot_update
from repro.kernels.flash_attention import (flash_attention, flash_prefill,
                                           prefill_blocks)
from repro.kernels.mca_matmul import mca_matmul_fixed, mca_matmul_ragged
from repro.models import build_model
from repro.serve import Engine

# StarCoder2-3B widths (configs/starcoder2_3b.py)
D_MODEL, N_HEADS, N_KV, D_HEAD = 3072, 24, 2, 128
SLOTS, MAX_LEN, SEQ = 4, 1024, 2048


@pytest.fixture(scope="module")
def topo():
    """The described topology, with the persistent compilation cache off:
    a described-chip compile is written to the cache but can never be read
    back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                 # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _copies_of(hlo: str, shape) -> int:
    """Ops in ``hlo`` other than the kernel that produce a ``shape``
    array: copies, relayout fusions, slices."""
    dims = r"\[" + ",".join(map(str, shape)) + r"\]"
    return len(re.findall(r"= \w+" + dims + r"\{[^}]*\} "
                          r"(?!custom-call|parameter|get-tuple-element|"
                          r"tuple|bitcast)[\w-]+\(", hlo))


def _kv_write(one_chip, cache_shape, dtype, telemetry=False):
    """The row write into both layers of a 2-layer stack of
    ``cache_shape`` (one layer's [B, S, ...]) caches."""
    new_shape = (2, cache_shape[0], 1) + tuple(cache_shape[2:])
    return jax.jit(
        lambda c, n, p: kv_slot_update(c, n, p, interpret=False,
                                       telemetry=telemetry),
        donate_argnums=0).lower(_sds(one_chip, (2,) + cache_shape, dtype),
                                _sds(one_chip, new_shape, dtype),
                                _sds(one_chip, (SLOTS,), jnp.int32))


@pytest.mark.parametrize("telemetry", [False, True])
def test_kv_slot_update_compiles(one_chip, telemetry):
    """The GQA cache write at StarCoder2-3B widths compiles to the kernel
    alone: the donated stack is written in place, never copied."""
    shape = (SLOTS, MAX_LEN, N_KV, D_HEAD)
    compiled = _assert_kernel(_kv_write(one_chip, shape, jnp.bfloat16,
                                        telemetry))
    assert _copies_of(compiled.as_text(), (2,) + shape) == 0
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("row,dtype", [
    ((2, 128), jnp.bfloat16), ((8, 256), jnp.float32), ((1, 128), jnp.float32),
    ((8, 128), jnp.int8), ((1, 128), jnp.bfloat16), ((6, 128), jnp.bfloat16),
    ((2, 128), jnp.int8), ((2, 64), jnp.float32), ((256,), jnp.bfloat16),
])
def test_row_dma_ok_matches_mosaic(one_chip, row, dtype):
    """``row_dma_ok`` (the kernel-vs-scatter dispatch rule) admits exactly
    the cache shapes whose row DMAs Mosaic compiles."""
    shape = (SLOTS, MAX_LEN) + row
    if cache_update.row_dma_ok(shape, dtype):
        _assert_kernel(_kv_write(one_chip, shape, dtype))
    else:
        with pytest.raises(Exception, match="aligned to tiling"):
            _kv_write(one_chip, shape, dtype).compile()


def test_mca_matmul_fixed_compiles(one_chip):
    m, d, f, r = SEQ, D_MODEL, N_KV * D_HEAD, 8
    _assert_kernel(mca_matmul_fixed.lower(
        _sds(one_chip, (m, d), jnp.bfloat16),
        _sds(one_chip, (d, f), jnp.bfloat16),
        _sds(one_chip, (r,), jnp.int32),
        _sds(one_chip, (r,), jnp.float32), interpret=False))


def test_mca_matmul_ragged_compiles(one_chip):
    m, d, f, r = SEQ, D_MODEL, N_KV * D_HEAD, 8
    tiles = m // 128
    _assert_kernel(mca_matmul_ragged.lower(
        _sds(one_chip, (m, d), jnp.bfloat16),
        _sds(one_chip, (d, f), jnp.bfloat16),
        _sds(one_chip, (tiles,), jnp.int32),
        _sds(one_chip, (tiles, r), jnp.int32),
        _sds(one_chip, (tiles, r), jnp.float32), interpret=False))


def test_flash_attention_compiles(one_chip):
    _assert_kernel(flash_attention.lower(
        _sds(one_chip, (1, N_HEADS, SEQ, D_HEAD), jnp.bfloat16),
        _sds(one_chip, (1, N_KV, SEQ, D_HEAD), jnp.bfloat16),
        _sds(one_chip, (1, N_KV, SEQ, D_HEAD), jnp.bfloat16),
        scale=D_HEAD ** -0.5, interpret=False))


@pytest.mark.parametrize("s", [128, 4096])
def test_flash_prefill_compiles(one_chip, s):
    """The insertion's attention at its smallest and largest buckets, with
    the tiles the dispatch picks, fits the kernel's VMEM."""
    bq, bk = prefill_blocks(s, D_HEAD)
    _assert_kernel(flash_prefill.lower(
        _sds(one_chip, (1, s, N_HEADS * D_HEAD), jnp.bfloat16),
        _sds(one_chip, (1, s, N_KV * D_HEAD), jnp.bfloat16),
        _sds(one_chip, (1, s, N_KV * D_HEAD), jnp.bfloat16),
        _sds(one_chip, (1,), jnp.int32), n_kv_heads=N_KV,
        scale=D_HEAD ** -0.5, block_q=bq, block_k=bk, interpret=False))


def test_attn_colmax_compiles(one_chip):
    _assert_kernel(attn_colmax.lower(
        _sds(one_chip, (1, N_HEADS, SEQ, D_HEAD), jnp.bfloat16),
        _sds(one_chip, (1, N_KV, SEQ, D_HEAD), jnp.bfloat16),
        _sds(one_chip, (1, N_HEADS, SEQ), jnp.float32),
        scale=D_HEAD ** -0.5, interpret=False))


def _burst(one_chip, n_layers=2, slots=SLOTS):
    model = build_model(get_config("starcoder2-3b", n_layers=n_layers))
    eng = Engine(model, None, batch_size=slots, max_len=MAX_LEN)
    place = lambda a: _sds(one_chip, a.shape, a.dtype)     # noqa: E731
    params = jax.tree.map(place, jax.eval_shape(model.init,
                                                jax.random.PRNGKey(0)))
    cache = jax.tree.map(place, jax.eval_shape(
        lambda: model.init_cache(slots, MAX_LEN)))
    vec = _sds(one_chip, (slots,), jnp.int32)
    return eng._make_burst(8, None).lower(
        params, _sds(one_chip, (slots, 1), jnp.int32), cache, vec, vec)


def _insertion(one_chip, s, n_layers=2, slots=SLOTS):
    """The slot insertion program (batch-1 prefill spliced into a slot)
    of an ``n_layers`` StarCoder2-3B cut at bucket ``s``; returns it
    compiled, and whether its attention took the flash kernel."""
    model = build_model(get_config("starcoder2-3b", n_layers=n_layers))
    eng = Engine(model, None, batch_size=slots, max_len=MAX_LEN)
    place = lambda a: _sds(one_chip, a.shape, a.dtype)     # noqa: E731
    params = jax.tree.map(place, jax.eval_shape(model.init,
                                                jax.random.PRNGKey(0)))
    cache = jax.tree.map(place, jax.eval_shape(
        lambda: model.init_cache(slots, MAX_LEN)))
    vec = _sds(one_chip, (slots,), jnp.int32)
    scalar = _sds(one_chip, (), jnp.int32)
    compiled = eng._prefill_into.lower(
        params, _sds(one_chip, (1, s), jnp.int32),
        _sds(one_chip, (1,), jnp.int32), cache,
        _sds(one_chip, (slots, 1), jnp.int32), vec, vec, scalar,
        scalar).compile()
    return compiled, eng._insert_on_kernel[(False, s)]


_FLASH = re.compile(r"%flash_prefill[.\d]* = \S+ custom-call\(")


@pytest.mark.parametrize("s,on_kernel", [(64, False), (512, True)])
def test_insertion_attention_dispatch_compiles(one_chip, monkeypatch, s,
                                               on_kernel):
    """The insertion program at StarCoder2-3B widths compiles with its
    attention on the flash kernel where the bucket tiles and on the scan
    below 128, and the Engine records which (the kernel-insertion
    counter's source)."""
    monkeypatch.setattr(kernel_ops, "_interpret", lambda: False)
    compiled, recorded = _insertion(one_chip, s)
    assert recorded == on_kernel
    assert bool(_FLASH.search(compiled.as_text())) == on_kernel


_KV_WRITE = re.compile(r"%kv_slot_update[.\d]* = \S+ custom-call\(")


def test_decode_burst_compiles(one_chip, monkeypatch):
    """The serve decode burst of a 2-layer full-width cut, with the KV
    write on its kernel path (the CPU backend would pick interpret mode,
    so the test steers the dispatch to compile the kernel).  Around the
    kernel the program must be the scatter fallback's: no extra copy of a
    layer's cache and no more temporary memory."""
    monkeypatch.setattr(kernel_ops, "_interpret", lambda: False)
    kernel = _assert_kernel(_burst(one_chip))
    assert _KV_WRITE.search(kernel.as_text())
    monkeypatch.setattr(cache_update, "row_dma_ok", lambda *a: False)
    scatter = _burst(one_chip).compile()
    assert not _KV_WRITE.search(scatter.as_text())
    layer = (SLOTS, MAX_LEN, N_KV, D_HEAD)
    assert (_copies_of(kernel.as_text(), layer)
            <= _copies_of(scatter.as_text(), layer))
    assert (kernel.memory_analysis().temp_size_in_bytes
            <= scatter.memory_analysis().temp_size_in_bytes)


def _stack_rewrites(hlo: str, shape) -> list:
    """Copies and dynamic-update-slices (alone or fused) in ``hlo`` that
    produce a ``shape`` array."""
    dims = r"\[" + ",".join(map(str, shape)) + r"\]"
    ops = re.findall(r"%([\w.-]+) = \w+" + dims + r"\{[^}]*\} ([\w-]+)\(",
                     hlo)
    return [name for name, op in ops
            if any(w in name or w in op
                   for w in ("copy", "dynamic-update-slice"))]


def test_decode_burst_keeps_cache_in_place(one_chip, monkeypatch):
    """The batch-gen cell's burst (all 30 layers, 64 slots x 1024, on the
    KV write and decode attention kernels) updates the layer-stacked cache
    in place: nothing copies or rebuilds a whole stacked leaf, and its
    temporaries stay under the size of one stacked K leaf."""
    monkeypatch.setattr(kernel_ops, "_interpret", lambda: False)
    n_layers, slots = 30, 64
    compiled = _assert_kernel(_burst(one_chip, n_layers, slots))
    hlo = compiled.as_text()
    k_leaf = (n_layers, slots, MAX_LEN, N_KV, D_HEAD)
    assert _stack_rewrites(hlo, k_leaf) == []
    assert _stack_rewrites(hlo, k_leaf[:3]) == []             # slot_pos
    k_bytes = 2 * n_layers * slots * MAX_LEN * N_KV * D_HEAD
    assert compiled.memory_analysis().temp_size_in_bytes < k_bytes
