"""Plain float32 reference of a dense decoder (Qwen1.5 / Qwen2 block).

Straight ``jax.numpy``: no kernels, no cache, no batching.  Per layer, on a
pre-norm residual stream:

    h = rmsnorm(x) * g1;  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = rope(q), rope(k)                  (rotate-half, base rope_theta)
    x = x + softmax(q k^T / sqrt(hd) + causal) v Wo
    h = rmsnorm(x) * g2;  x = x + (silu(h Wg) * (h Wu)) Wd
    logits = rmsnorm(x) * gf  W_head

Departures, each as the configuration states it: a norm gain is stored as
``1 + scale``; the serving cache holds keys and values in
``kv_cache_dtype``, so positions decoded through the cache attend over
keys and values rounded to it, while prompt positions (prefilled in one
pass) attend over them unrounded.

The layer's parts, ``attention`` and ``swiglu``, are functions of their
own, for a reference of mixed layers to compose.  The weights are read by
name from the parameter tree the benchmark drew (``chipbench.weights``);
nothing of the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import mm, rms_norm


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend(q, k, v, precision):
    t = q.shape[0]
    s = mm("qhd,khd->hqk", q, k, precision) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm("hqk,khd->qhd", p, v, precision)


def attention(a, h, dims, prompt_len, precision):
    """Causal self-attention of one sequence's normed hidden states ``h``
    (T, d) with the layer's weights ``a`` (``wq``, ``wk``, ``wv`` with
    their biases, ``wo``): RoPE, grouped K/V heads repeated over the query
    heads; positions from ``prompt_len`` on attend over K and V rounded to
    ``kv_cache_dtype``."""
    t = h.shape[0]
    hq, hkv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    pos = jnp.arange(t)
    decode_rows = (pos >= prompt_len)[:, None, None]
    kv_dt = jnp.dtype(dims.kv_cache_dtype)
    q = (mm("td,de->te", h, a["wq"]["w"], precision) + a["wq"]["b"]).reshape(t, hq, hd)
    k = (mm("td,de->te", h, a["wk"]["w"], precision) + a["wk"]["b"]).reshape(t, hkv, hd)
    v = (mm("td,de->te", h, a["wv"]["w"], precision) + a["wv"]["b"]).reshape(t, hkv, hd)
    q, k = _rope(q, pos, dims.rope_theta), _rope(k, pos, dims.rope_theta)
    rep = hq // hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    fresh = _attend(q, k, v, precision)
    cached = _attend(q, k.astype(kv_dt).astype(jnp.float32),
                     v.astype(kv_dt).astype(jnp.float32), precision)
    o = jnp.where(decode_rows, cached, fresh).reshape(t, hq * hd)
    return mm("te,ed->td", o, a["wo"]["w"], precision)


def swiglu(m, h, precision):
    """``(silu(h Wg) * (h Wu)) Wd`` with the layer's weights ``m``."""
    g = mm("td,df->tf", h, m["gate"]["w"], precision)
    u = mm("td,df->tf", h, m["up"]["w"], precision)
    return mm("tf,fd->td", jax.nn.silu(g) * u, m["down"]["w"], precision)


def hidden(params, dims, tokens, prompt_len, precision="highest"):
    """Final normed hidden states (T, d) of one sequence.  ``tokens`` may be
    padded at the end: attention is causal, so padding changes nothing
    before it."""

    def layer(x, p):
        h = rms_norm(x, p["norm1"], dims.norm_eps)
        x = x + attention(p["attn"], h, dims, prompt_len, precision)
        h = rms_norm(x, p["norm2"], dims.norm_eps)
        return x + swiglu(p["mlp"], h, precision), None

    x = params["embed"]["w"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, params["layers"]["seg0_dense"])
    return rms_norm(x, params["final_norm"], dims.norm_eps)


def head(params, dims):
    """The vocabulary head as a (d, V) matrix."""
    if dims.tie_embeddings:
        return params["embed"]["w"].T
    return params["unembed"]["w"]
