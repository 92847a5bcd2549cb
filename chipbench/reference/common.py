"""What the references share: the contraction at a stated precision, the
norm, and the logits of chosen rows, computed in blocks so that a long
sequence's logits never sit in memory whole.

Precisions: ``highest`` (f32, the reference), and the controls a step below
a configuration's own: ``high`` (three bf16 passes: hi*hi + hi*lo + lo*hi),
``bf16`` (one pass) and ``fp8`` (operands rounded to float8_e4m3fn).  The
controls split and round their operands explicitly and sum exact products
in f32, so they read the same on any backend.

A reference is ``reference/<family>.py``, found by the configuration
file's ``family``, with ``hidden(params, dims, tokens, prompt_len,
precision)`` and ``head(params, dims)``.  ``dims`` (``check.ref_dims``)
holds the program's ArchConfig fields, which ``spec.ARCH_KEYS`` maps from
the configuration file, and the file itself, as loaded, under
``dims.published``.  A reference takes a source constant that the
ArchConfig does not map through ``spec.ARCH_KEYS`` (a multiplier, a
positional encoding) from ``dims.published``, by the source's own key,
never from a field of the program: an error in the program's registry must
read as a difference, not agree with itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _round(x, dtype):
    return x.astype(dtype).astype(jnp.float32)


def _dot(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def mm(eq: str, a, b, precision: str):
    if precision == "highest":
        return _dot(eq, a, b)
    if precision == "high":
        a_hi, b_hi = _round(a, jnp.bfloat16), _round(b, jnp.bfloat16)
        a_lo, b_lo = _round(a - a_hi, jnp.bfloat16), _round(b - b_hi, jnp.bfloat16)
        return _dot(eq, a_hi, b_hi) + (_dot(eq, a_hi, b_lo) + _dot(eq, a_lo, b_hi))
    dtype = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[precision]
    return _dot(eq, _round(a, dtype), _round(b, dtype))


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def logits_rows(h_rows, w_head, precision: str):
    """Logits (R, V) of some rows of final hidden states."""
    return mm("rd,dv->rv", h_rows, w_head, precision)
