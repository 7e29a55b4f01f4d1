"""Operations and bytes the algorithm needs, computed from shapes.

Model FLOPs count each multiply-add as 2 and leave out what an
implementation adds on top (padding rows of the vocabulary, recomputation
under rematerialization, masked-out halves of a causal score matrix), so a
share of the chip's peak computed from them cannot pass 100% unless the
time leaves out part of the work.  ``m`` is a configuration's program
dict (``chipbench/configs/<name>.json`` under ``program.model``).
"""
from __future__ import annotations


def layer_params(m: dict) -> int:
    """Matmul weights of one block: q, k, v, o and the two MLP matrices."""
    d, h, hkv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["d_head"], m["d_ff"])
    return d * h * dh + 2 * d * hkv * dh + h * dh * d + 2 * d * f


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Bytes of the weights a forward pass reads: every block's matrices
    and the (tied) embedding table, padded to a multiple of 128 rows."""
    vp = -(-m["vocab_size"] // 128) * 128
    return itemsize * (m["n_layers"] * layer_params(m) + vp * m["d_model"])


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Cache bytes one position holds over all layers (K and V)."""
    return itemsize * 2 * m["n_layers"] * m["n_kv_heads"] * m["d_head"]


def attn_flops(m: dict, queries_keys: float) -> float:
    """Scores and weighted values over ``queries_keys`` (query, key)
    pairs in every layer and head."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["d_head"] * queries_keys


def insert_flops(m: dict, s: int) -> float:
    """One causal prefill of ``s`` positions that scores the last one."""
    return (2.0 * s * m["n_layers"] * layer_params(m)
            + attn_flops(m, s * (s + 1) / 2)
            + 2.0 * m["d_model"] * m["vocab_size"])


def decode_flops(m: dict, context: float) -> float:
    """One decoded token that attends to ``context`` cached positions."""
    return (2.0 * m["n_layers"] * layer_params(m) + attn_flops(m, context)
            + 2.0 * m["d_model"] * m["vocab_size"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward (three forwards) of one token of a
    bidirectional encoder trained on every position of ``seq``."""
    fwd = (2.0 * m["n_layers"] * layer_params(m) + attn_flops(m, seq)
           + 2.0 * m["d_model"] * m["vocab_size"])
    return 3.0 * fwd
