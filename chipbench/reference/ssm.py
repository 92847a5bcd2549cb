"""Plain float32 reference of a Mamba-2 decoder (arXiv:2405.21060), in the
recurrent form, one token after another.

Per layer, on a pre-norm residual stream (one group of B and C):

    z, xBC, dt = split(rmsnorm(x) * g1  W_in)
    x, B, C = split(silu(causal_depthwise_conv(xBC) + conv_bias))
    dt = softplus(dt + dt_bias);  A = -exp(a_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        (per head: P x N)
    y_t = h_t C_t + D x_t
    x = x + rmsnorm(y * silu(z)) * g  W_out
    logits = rmsnorm(x) * gf  W_head

The serving program computes prefill in the chunked dual form; this is the
recurrence it must equal.  Departures, as the configuration states them: a
norm gain is stored as ``1 + scale``, and the conv bias is added only where
the weight tree has one (``conv_b``): while the program has no conv bias,
neither side adds one.  The layer's ``mixer`` is a function of its own, for
a reference of mixed layers to compose.  Weights are read by name from the
tree the benchmark drew.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import mm, rms_norm


def _conv(x, w):
    """Causal depthwise conv: x (T, C), w (K, C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[i:i + x.shape[0]] * w[i] for i in range(k))


def mixer(s, u, dims, precision):
    """The Mamba-2 mixer of one sequence's normed hidden states ``u`` (T, d)
    with the layer's weights ``s`` (``in_proj`` to ``out_proj``; the conv
    bias ``conv_b`` where the tree has one), one group of B and C."""
    d, n = dims.d_model, dims.ssm_state
    di = dims.ssm_expand * d
    nh, hp = di // dims.ssm_head_dim, dims.ssm_head_dim
    t = u.shape[0]
    proj = mm("td,de->te", u, s["in_proj"]["w"], precision)
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * n], proj[:, 2 * di + 2 * n:]
    xbc = _conv(xbc, s["conv_w"])
    if "conv_b" in s:
        xbc = xbc + s["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :di].reshape(t, nh, hp)
    bm, cm = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + s["dt_bias"])
    a = -jnp.exp(s["a_log"])

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = h * jnp.exp(dtt * a)[:, None, None] + (dtt[:, None] * xt)[..., None] * bt
        return h, mm("hpn,n->hp", h, ct, precision)

    h0 = jnp.zeros((nh, hp, n), jnp.float32)
    _, y = jax.lax.scan(step, h0, (xs, dt, bm, cm))
    y = (y + xs * s["d_skip"][None, :, None]).reshape(t, di)
    y = rms_norm(y * jax.nn.silu(z), s["norm_scale"], dims.norm_eps)
    return mm("te,ed->td", y, s["out_proj"]["w"], precision)


def hidden(params, dims, tokens, prompt_len, precision="highest"):
    """Final normed hidden states (T, d) of one sequence (``prompt_len`` is
    unused: the recurrence has no cache of its own to round)."""
    del prompt_len

    def layer(x, p):
        u = rms_norm(x, p["norm1"], dims.norm_eps)
        return x + mixer(p["ssm"], u, dims, precision), None

    x = params["embed"]["w"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, params["layers"]["seg0_ssm"])
    return rms_norm(x, params["final_norm"], dims.norm_eps)


def head(params, dims):
    if dims.tie_embeddings:
        return params["embed"]["w"].T
    return params["unembed"]["w"]
