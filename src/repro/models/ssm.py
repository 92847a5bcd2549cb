"""Mamba2 / SSD (state-space duality) block  [arXiv:2405.21060].

The SSD chunked algorithm is matmul-dominated — exactly the workload the
paper's engine targets: the intra-chunk quadratic term and the inter-chunk
state GEMMs route through RMPM ('ssd' op class).  The recurrent gate/decay
algebra itself is elementwise (not a GEMM) and runs in f32 — the technique is
N/A to the scan, as recorded in DESIGN.md section Arch-applicability.

Train: chunked dual form (quadratic intra-chunk + linear inter-chunk scan).
Decode: O(1) recurrent state update per token.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.layers import Params, causal_conv1d, dense_init, pein

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SSMState:
    conv: Array  # (B, K-1, conv_channels)
    ssm: Array  # (B, H, P, N)


def _dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads


def mamba2_init(key, cfg) -> Params:
    d, n = cfg.d_model, cfg.ssm_state
    d_inner, n_heads = _dims(cfg)
    ks = jax.random.split(key, 4)
    # in_proj -> [z (gate), x, B, C, dt]
    d_proj = 2 * d_inner + 2 * n + n_heads
    conv_ch = d_inner + 2 * n  # conv over x, B, C
    return {
        "in_proj": dense_init(ks[0], d, d_proj),
        "conv_w": jax.random.normal(ks[1], (cfg.ssm_conv, conv_ch), jnp.float32) * 0.2,
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads, dtype=jnp.float32)),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm_scale": jnp.zeros((d_inner,), jnp.float32),
        "out_proj": dense_init(ks[2], d_inner, d),
    }


def _segsum(x: Array) -> Array:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} x[..., k] (i >= j)."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def _ssd_chunked(xh, dt, a, bmat, cmat, cfg, h0=None):
    """SSD dual form over chunks.

    xh: (B, S, H, P); dt: (B, S, H); a: (H,) negative decay rates;
    bmat/cmat: (B, S, N).  Returns (y, final_state (B, H, P, N)).
    """
    policy = cfg.policy
    b, s, h, p = xh.shape
    n = bmat.shape[-1]
    q = min(cfg.ssm_chunk, s)
    assert s % q == 0, (s, q)
    c = s // q
    xs = xh.reshape(b, c, q, h, p)
    dts = dt.reshape(b, c, q, h)
    bs = bmat.reshape(b, c, q, n)
    cs = cmat.reshape(b, c, q, n)

    da = dts * a[None, None, None, :]  # (B, C, Q, H) negative
    da_cum = jnp.cumsum(da, axis=2)

    # --- intra-chunk (quadratic, matmul-heavy) ---
    l_mat = jnp.exp(_segsum(da.transpose(0, 1, 3, 2)))  # (B, C, H, Q, Q)
    scores = pein("bcqn,bckn->bcqk", cs, bs, "ssd", policy)  # (B, C, Q, Q)
    gated = scores[:, :, None] * l_mat  # (B, C, H, Q, Q)
    xdt = xs * dts[..., None]  # (B, C, Q, H, P)
    y_intra = pein("bchqk,bckhp->bcqhp", gated, xdt, "ssd", policy)

    # --- chunk states ---
    decay_to_end = jnp.exp(da_cum[:, :, -1:, :] - da_cum)  # (B, C, Q, H)
    states = pein(
        "bcqn,bcqhp->bchpn", bs, xdt * decay_to_end[..., None], "ssd", policy
    )  # (B, C, H, P, N)

    # --- inter-chunk recurrence over C (sequential scan, tiny) ---
    chunk_decay = jnp.exp(da_cum[:, :, -1, :])  # (B, C, H)

    def step(carry, inp):
        st, dec = inp  # (B, H, P, N), (B, H)
        new = carry * dec[..., None, None] + st
        return new, carry  # emit PREVIOUS state (state entering the chunk)

    init = (
        h0 if h0 is not None else jnp.zeros((b, h, p, n), jnp.float32)
    )
    final, prev_states = jax.lax.scan(
        step,
        init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (B, C, H, P, N)

    # --- inter-chunk output ---
    decay_from_start = jnp.exp(da_cum)  # (B, C, Q, H)
    c_gated = cs[:, :, :, None, :] * decay_from_start[..., None]  # (B,C,Q,H,N)
    y_inter = pein("bcqhn,bchpn->bcqhp", c_gated, prev_states, "ssd", policy)

    y = (y_intra + y_inter).reshape(b, s, h, p)
    return y, final


def mamba2_apply(
    p: Params, x: Array, cfg, state: SSMState | None = None
) -> tuple[Array, SSMState | None]:
    """x: (B, S, d_model).  state!=None -> decode (S small, sequential)."""
    policy = cfg.policy
    proj = pein("bsd,de->bse", x, p["in_proj"]["w"], "ssm_in", policy)
    # the mixer between the projections (conv, SSD scan or recurrence,
    # state update, gated norm) under one scope: its 'ssd' contractions
    # count as the mixer, not as weight GEMMs
    with jax.named_scope("ssm"):
        y, new_state = _mixer(proj, p, cfg, state)
    out = pein("bse,ed->bsd", y, p["out_proj"]["w"], "ssm_out", policy)
    return out, new_state


def _mixer(proj: Array, p: Params, cfg, state: SSMState | None):
    b, s, _ = proj.shape
    d_inner, n_heads = _dims(cfg)
    n = cfg.ssm_state
    z, xbc, dt_raw = jnp.split(proj, [d_inner, 2 * d_inner + 2 * n], axis=-1)
    conv_in = xbc  # (B, S, d_inner + 2N)
    conv_out, conv_state = causal_conv1d(
        conv_in, p["conv_w"], state.conv if state is not None else None
    )
    conv_out = jax.nn.silu(conv_out)
    xh, bmat, cmat = jnp.split(conv_out, [d_inner, d_inner + n], axis=-1)
    xh = xh.reshape(b, s, n_heads, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"][None, None, :])  # (B, S, H)
    a = -jnp.exp(p["a_log"])  # (H,) negative

    if state is None:
        y, final = _ssd_chunked(xh, dt, a, bmat, cmat, cfg)
        new_state = None
    elif s > 1 and s % min(cfg.ssm_chunk, s) == 0:
        # multi-token prefill: the chunked DUAL form with the carried state —
        # the sequential recurrence would round-trip the (B,H,P,N) state
        # through HBM once per token (measured 5.5e14 B/device at 32k,
        # EXPERIMENTS.md section Perf cell E)
        y, final = _ssd_chunked(xh, dt, a, bmat, cmat, cfg, h0=state.ssm)
        new_state = SSMState(conv=conv_state, ssm=final)
    else:
        # recurrent: h = exp(dt*a) h + dt * B x ; y = C h   (per step)
        def step(h, inp):
            xt, dtt, bt, ct = inp  # (B,H,P), (B,H), (B,N), (B,N)
            decay = jnp.exp(dtt * a[None, :])[..., None, None]
            h = h * decay + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
            yt = jnp.einsum("bhpn,bn->bhp", h, ct)
            return h, yt

        final, ys = jax.lax.scan(
            step,
            state.ssm,
            (
                xh.transpose(1, 0, 2, 3),
                dt.transpose(1, 0, 2),
                bmat.transpose(1, 0, 2),
                cmat.transpose(1, 0, 2),
            ),
        )
        y = ys.transpose(1, 0, 2, 3)  # (B, S, H, P)
        new_state = SSMState(conv=conv_state, ssm=final)

    y = y + xh * p["d_skip"][None, None, :, None]
    y = y.reshape(b, s, d_inner)
    # gated RMSNorm (Mamba2)
    y = y * jax.nn.silu(z)
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + cfg.norm_eps) * (1.0 + p["norm_scale"])
    return y, new_state


def ssm_state_init(cfg, batch: int) -> SSMState:
    d_inner, n_heads = _dims(cfg)
    conv_ch = d_inner + 2 * cfg.ssm_state
    return SSMState(
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), jnp.float32),
        ssm=jnp.zeros((batch, n_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
    )
