"""Mamba-2 mixer (SSD) with one group of B and C: the in and out
projections, the f32 SSM state and conv window a row holds, and the state's
readout and update per token (the linear-time recurrent form)."""
from __future__ import annotations


def _inner(d) -> tuple[int, int]:
    """(d_inner, heads)"""
    di = d.ssm_expand * d.d_model
    return di, di // d.ssm_head_dim


def gemms(d):
    di, h = _inner(d)
    return {"ssm_in": [(d.d_model, 2 * di + 2 * d.ssm_state + h)],
            "ssm_out": [(di, d.d_model)]}


def state_bytes(d):
    di, h = _inner(d)
    ssm = h * d.ssm_head_dim * d.ssm_state
    conv = (d.ssm_conv - 1) * (di + 2 * d.ssm_state)
    return 4 * (ssm + conv)


def decode_bytes(d, context):
    """The row's state read and written."""
    return 2 * state_bytes(d)


def decode_flops(d, context):
    return 2 * 2 * d.ssm_expand * d.d_model * d.ssm_state


def prefill_flops(d, prompt_len):
    return prompt_len * decode_flops(d, 1)
