"""Attention: the q, k, v and output projections, the K/V cache, and the
scores and weighted values of each query over its context."""
from __future__ import annotations

KV_BYTES = {"bfloat16": 2, "int8": 1, "float32": 4}


def gemms(d):
    q, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    return {"qkv": [(d.d_model, q), (d.d_model, kv), (d.d_model, kv)],
            "out": [(q, d.d_model)]}


def token_bytes(d):
    return 2 * d.n_kv_heads * d.head_dim * KV_BYTES[d.kv_cache_dtype]


def decode_bytes(d, context):
    """The row's cache read, its new entry included."""
    return context * token_bytes(d)


def decode_flops(d, context):
    return 2 * 2 * d.n_heads * d.head_dim * context


def prefill_flops(d, prompt_len):
    """Position ``i`` (from 1) attends over ``i`` positions."""
    return decode_flops(d, prompt_len * (prompt_len + 1) // 2)
