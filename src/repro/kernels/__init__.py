"""Pallas TPU kernels for the MCA hot loops (interpret-mode on CPU).

The paper's own CUDA kernel is a fused gather-GEMM for the sampled
projection; kernels here are its TPU-native counterparts (see DESIGN.md):
  mca_matmul      block-sampled matmul, scalar-prefetch DMA gather
  flash_attention online-softmax fwd producing LSE (the colmax enabler);
                  flash_prefill, the same kernel on a cache-filling
                  prefill (causal, left-padded, no LSE)
  attn_colmax     Eq.9 r-driver: max_i A[i,j] in O(n) memory
  kv_slot_update  per-row KV-cache write (per-slot continuous batching)
  decode_attention one-token attention over a layer-stacked KV cache
"""
from .ops import (attn_colmax, decode_attention, flash_attention,
                  flash_prefill, kv_slot_update, mca_matmul,
                  mca_matmul_ragged)

__all__ = ["attn_colmax", "decode_attention", "flash_attention",
           "flash_prefill", "kv_slot_update", "mca_matmul",
           "mca_matmul_ragged"]
