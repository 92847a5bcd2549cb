"""Public wrapper for the limb_matmul Pallas kernel.

``limb_matmul`` is a thin non-jit shell that resolves the backend-aware
``interpret`` default (interpret on CPU, compiled Mosaic elsewhere — see
``kernels.blocking.default_interpret``) and calls the jitted ``_limb_matmul``
body.  The resolution happens OUTSIDE the jit boundary so an explicit
override or a different backend is never frozen into a cached trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.blocking import clamp_block, pad_to_block, resolve_interpret
from repro.kernels.limb_matmul.limb_matmul import limb_matmul_pallas


def limb_matmul(
    a: jax.Array,
    b: jax.Array,
    k: int = 3,
    *,
    rounding: str = "rne",
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Multi-precision matmul a (..., K) @ b (K, N) via the fused Pallas
    kernel; pads to block multiples and strips the padding.

    ``interpret=None`` (default) interprets on CPU and compiles elsewhere;
    pass a bool to force either.  Only RNE limb extraction is fused; the
    paper's GRTE rounding runs through kernels/quantize_mantissa first.
    """
    return _limb_matmul(
        a, b, k, rounding=rounding, bm=bm, bn=bn, bk=bk,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("k", "bm", "bn", "bk", "interpret", "rounding"))
def _limb_matmul(
    a: jax.Array,
    b: jax.Array,
    k: int,
    *,
    rounding: str,
    bm: int,
    bn: int,
    bk: int,
    interpret: bool,
) -> jax.Array:
    return _limb_matmul_vjp(a, b, (k, rounding, bm, bn, bk, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _limb_matmul_vjp(a, b, cfg):
    """Differentiable core: ``pallas_call`` has no usable JVP when compiled
    for the TPU, so the backward pass is written out instead.  It runs the
    same kernel at the same limb count and rounding on the transposed
    operands (``da = g @ b.T``, ``db = a.T @ g``), keeping the paper's
    multiplier in both passes; the rounding pre-pass is straight-through.
    """
    return _forward(a, b, *cfg)


def _limb_matmul_fwd(a, b, cfg):
    return _forward(a, b, *cfg), (a, b)


def _limb_matmul_bwd(cfg, res, g):
    a, b = res
    k, rounding, bm, bn, bk, interpret = cfg
    kdim, n = b.shape
    g = g.astype(jnp.float32)
    # each backward GEMM keeps the forward's tile roles: the contraction of
    # da is N (block bn), the contraction of db is M (block bm)
    da = _limb_matmul_vjp(g, b.astype(jnp.float32).T,
                          (k, rounding, bm, bk, bn, interpret))
    db = _limb_matmul_vjp(a.reshape(-1, kdim).astype(jnp.float32).T,
                          g.reshape(-1, n), (k, rounding, bk, bn, bm, interpret))
    return da.astype(a.dtype), db.astype(b.dtype)


_limb_matmul_vjp.defvjp(_limb_matmul_fwd, _limb_matmul_bwd)


def _forward(a, b, k, rounding, bm, bn, bk, interpret):
    if rounding != "rne":
        from repro.kernels.quantize_mantissa.ops import quantize_mantissa_op

        a = quantize_mantissa_op(a, 8 * k - 1, rounding, interpret=interpret)
        b = quantize_mantissa_op(b, 8 * k - 1, rounding, interpret=interpret)
    lead = a.shape[:-1]
    kdim = a.shape[-1]
    n = b.shape[-1]
    a2 = a.reshape(-1, kdim).astype(jnp.float32)
    m = a2.shape[0]
    bm_, bn_, bk_ = clamp_block(bm, m), clamp_block(bn, n), clamp_block(bk, kdim)
    a2 = pad_to_block(a2, bm_, bk_)
    b2 = pad_to_block(b.astype(jnp.float32), bk_, bn_)
    out = limb_matmul_pallas(a2, b2, k, bm=bm_, bn=bn_, bk=bk_, interpret=interpret)
    return out[:m, :n].reshape(*lead, n)
