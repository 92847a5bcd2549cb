"""Public wrappers for the partitioned tile_matmul Pallas kernel.

Entry points (all accept arbitrary leading batch dims on ``a``):

* ``tile_matmul(a, b, mode_map)``      — explicit per-tile map
* ``tile_matmul_mode(a, b, mode)``     — static uniform map from a Mode
  (bit-identical to ``limb_matmul`` at the same blocks, by construction)
* ``tile_matmul_runtime(a, b, scalar)``— traced mode scalar broadcast into a
  uniform map: the single-dispatch replacement for the ``lax.switch`` in
  ``mp_matmul_runtime`` (zero-recompile across mode changes)
* ``tile_matmul_auto(a, b, budget)``   — magnitude-statistics map (see
  ``tile_policy.magnitude_map``)

``interpret=None`` resolves backend-aware at call time (interpret on CPU,
compiled Mosaic elsewhere); the resolution lives OUTSIDE the jit boundary so
it is never frozen into a cached trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.precision import F32_MODES, MODE_LIMBS, Mode
from repro.kernels.blocking import ceil_to, clamp_block, pad_to_block, resolve_interpret
from repro.kernels.tile_matmul.tile_matmul import tile_matmul_pallas

DEFAULT_BLOCK = (128, 128, 512)
F32_KMAX = max(MODE_LIMBS[m] for m in F32_MODES)  # 3 limbs (M24)


def tile_grid(
    m: int, n: int, kdim: int, *, bm: int = 128, bn: int = 128, bk: int = 512
) -> tuple[tuple[int, int, int], tuple[int, int, int]]:
    """Clamp blocks to the (flattened) problem shape and return
    ``((gm, gn, gk), (bm, bn, bk))`` — the mode-map grid is ``(gm, gn)`` or
    ``(gm, gn, gk)``.  This is the single source of truth for map shapes;
    ``tile_policy`` builds maps against it and ``tile_matmul`` validates
    against it.
    """
    bm_, bn_, bk_ = clamp_block(bm, m), clamp_block(bn, n), clamp_block(bk, kdim)
    grid = (ceil_to(m, bm_) // bm_, ceil_to(n, bn_) // bn_, ceil_to(kdim, bk_) // bk_)
    return grid, (bm_, bn_, bk_)


@functools.partial(
    jax.jit, static_argnames=("kmax", "rounding", "bm", "bn", "bk", "interpret")
)
def _tile_matmul(a, b, mode_map, *, kmax, rounding, bm, bn, bk, interpret):
    return _tile_matmul_vjp(a, b, mode_map, (kmax, rounding, bm, bn, bk, interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tile_matmul_vjp(a, b, mode_map, cfg):
    """Differentiable core (``pallas_call`` has no usable JVP when compiled
    for the TPU).  The backward pass replays every forward block product at
    its own mode: ``da = g @ b.T`` and ``db = a.T @ g`` run the same kernel
    with 3D maps whose entry for (output tile, contraction slab) is the
    forward map's entry for that (i, j, kk) block.  The map itself gets no
    cotangent; the rounding pre-pass is straight-through.
    """
    return _forward(a, b, mode_map, *cfg)


def _tile_matmul_fwd(a, b, mode_map, cfg):
    return _forward(a, b, mode_map, *cfg), (a, b, mode_map)


def _tile_matmul_bwd(cfg, res, g):
    a, b, mode_map = res
    kmax, rounding, bm, bn, bk, interpret = cfg
    kdim, n = b.shape
    a2 = a.reshape(-1, kdim).astype(jnp.float32)
    (gm, gn, gk), (bm_, bn_, bk_) = tile_grid(a2.shape[0], n, kdim, bm=bm, bn=bn, bk=bk)
    if mode_map.ndim == 2:
        mode_map = jnp.broadcast_to(mode_map[:, :, None], (gm, gn, gk))
    g = g.astype(jnp.float32)
    # da[i, kk] sums over j, db[kk, j] sums over i: the clamped forward
    # blocks are reused, so both grids line up with the forward map's axes
    da = _tile_matmul_vjp(g, b.astype(jnp.float32).T,
                          jnp.transpose(mode_map, (0, 2, 1)),
                          (kmax, rounding, bm_, bk_, bn_, interpret))
    db = _tile_matmul_vjp(a2.T, g.reshape(-1, n),
                          jnp.transpose(mode_map, (2, 1, 0)),
                          (kmax, rounding, bk_, bn_, bm_, interpret))
    return da.astype(a.dtype), db.astype(b.dtype), None


_tile_matmul_vjp.defvjp(_tile_matmul_fwd, _tile_matmul_bwd)


def _forward(a, b, mode_map, kmax, rounding, bm, bn, bk, interpret):
    if rounding != "rne":
        from repro.kernels.quantize_mantissa.ops import quantize_mantissa_op

        # GRTE applies at the coarsest retained limb width (kmax), matching
        # limb_matmul's pre-pass for uniform maps; identity for kmax >= 3.
        keep = 8 * kmax - 1
        a = quantize_mantissa_op(a, keep, rounding, interpret=interpret)
        b = quantize_mantissa_op(b, keep, rounding, interpret=interpret)
    lead = a.shape[:-1]
    kdim = a.shape[-1]
    n = b.shape[-1]
    a2 = a.reshape(-1, kdim).astype(jnp.float32)
    m = a2.shape[0]
    grid, (bm_, bn_, bk_) = tile_grid(m, n, kdim, bm=bm, bn=bn, bk=bk)
    expect = grid[:2] if mode_map.ndim == 2 else grid
    if mode_map.ndim not in (2, 3) or mode_map.shape != expect:
        raise ValueError(
            f"mode_map shape {mode_map.shape} != tile grid {expect} for "
            f"flattened matmul ({m}, {kdim}) @ ({kdim}, {n}) at blocks "
            f"({bm_}, {bn_}, {bk_})"
        )
    a2 = pad_to_block(a2, bm_, bk_)
    b2 = pad_to_block(b.astype(jnp.float32), bk_, bn_)
    out = tile_matmul_pallas(
        a2, b2, mode_map, kmax=kmax, bm=bm_, bn=bn_, bk=bk_, interpret=interpret
    )
    return out[:m, :n].reshape(*lead, n)


def tile_matmul(
    a: jax.Array,
    b: jax.Array,
    mode_map: jax.Array,
    *,
    kmax: int = F32_KMAX,
    rounding: str = "rne",
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Multi-precision matmul a (..., K) @ b (K, N) with a per-tile mode map.

    ``mode_map`` entries are f32-ladder Mode values (== limb counts, in
    [1, kmax]); shape must match ``tile_grid`` for the flattened problem.
    The map is a traced argument: new maps reuse the compiled kernel.
    """
    return _tile_matmul(
        a,
        b,
        mode_map,
        kmax=kmax,
        rounding=rounding,
        bm=bm,
        bn=bn,
        bk=bk,
        interpret=resolve_interpret(interpret),
    )


def tile_matmul_mode(
    a: jax.Array,
    b: jax.Array,
    mode: Mode,
    *,
    rounding: str = "rne",
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Uniform static-mode tile matmul — bit-identical to
    ``limb_matmul(k=MODE_LIMBS[mode])`` at the same blocks (kmax is set to
    the mode's limb count, so the executed passes, their order, and the GRTE
    pre-pass width all coincide with the uniform kernel)."""
    mode = Mode(mode)
    if mode not in F32_MODES:
        raise ValueError(f"tile impl supports the f32 ladder {F32_MODES}, got {mode!r}")
    k = MODE_LIMBS[mode]
    lead_m = 1
    for d in a.shape[:-1]:
        lead_m *= d
    grid, _ = tile_grid(lead_m, b.shape[-1], a.shape[-1], bm=bm, bn=bn, bk=bk)
    mode_map = jnp.full(grid[:2], k, dtype=jnp.int32)
    return tile_matmul(
        a, b, mode_map, kmax=k, rounding=rounding, bm=bm, bn=bn, bk=bk,
        interpret=interpret,
    )


def tile_matmul_runtime(
    a: jax.Array,
    b: jax.Array,
    mode_scalar: jax.Array,
    *,
    rounding: str = "rne",
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Run-time reconfigurable tile matmul: a TRACED f32-ladder mode scalar
    (e.g. from ``repro.adapt``'s ModeTable) broadcast into a uniform map.

    One fused dispatch at every mode — this is what replaces the N-branch
    ``lax.switch`` of ``mp_matmul_runtime``; mode changes touch only the map
    values, never the compiled executable.
    """
    lead_m = 1
    for d in a.shape[:-1]:
        lead_m *= d
    grid, _ = tile_grid(lead_m, b.shape[-1], a.shape[-1], bm=bm, bn=bn, bk=bk)
    k = jnp.clip(jnp.asarray(mode_scalar, jnp.int32), 1, F32_KMAX)
    mode_map = jnp.full(grid[:2], 1, dtype=jnp.int32) * k
    return tile_matmul(
        a, b, mode_map, kmax=F32_KMAX, rounding=rounding, bm=bm, bn=bn, bk=bk,
        interpret=interpret,
    )


def tile_matmul_auto(
    a: jax.Array,
    b: jax.Array,
    budget: float,
    *,
    relative: bool = True,
    per_k: bool = False,
    max_mode: Mode = Mode.M24,
    rounding: str = "rne",
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Magnitude-statistics tile matmul: per-tile operand abs-max picks the
    cheapest mode meeting the per-tile error ``budget`` (see
    ``tile_policy.magnitude_map``), then one fused dispatch runs the map."""
    from repro.kernels.tile_matmul.tile_policy import magnitude_map

    mode_map = magnitude_map(
        a, b, budget, relative=relative, per_k=per_k, max_mode=max_mode,
        bm=bm, bn=bn, bk=bk,
    )
    return tile_matmul(
        a, b, mode_map, kmax=MODE_LIMBS[Mode(max_mode)], rounding=rounding,
        bm=bm, bn=bn, bk=bk, interpret=interpret,
    )
