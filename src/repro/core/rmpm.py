"""RMPM — the Run-time-reconfigurable Multi-Precision Matmul engine (C1+C2).

This is the paper's reconfigurable floating-point multiplier, lifted from a
single FP multiply to the TPU-native unit of work: a matmul on the MXU.

  * ``mp_matmul(a, b, mode)``         — static-mode k-limb matmul
  * ``mp_matmul_runtime(a, b, mode)`` — runtime mode scalar, one compiled
        executable, ``lax.switch`` selects the active branch (the paper's
        "unused multipliers are shut down"; no recompile <-> no re-synthesis)
  * ``mp_einsum(eq, a, b, mode)``     — same engine for arbitrary
        contractions (attention scores, attention-value, SSD blocks, ...)

Implementation paths:
  * ``impl='xla'``    — limb algebra expressed as jnp dots; XLA lowers each
        pass to an MXU matmul (this is what the dry-run/roofline measures).
  * ``impl='pallas'`` — fused limb-extraction + multi-pass matmul kernel
        (kernels/limb_matmul); TPU target, validated in interpret mode.
  * ``impl='tile'``   — partitioned-SIMD kernel (kernels/tile_matmul): a
        per-tile mode map rides along as a runtime argument, so one fused
        dispatch serves every f32-ladder mode (and mixed-mode maps) with no
        ``lax.switch`` — uniform maps are bit-identical to impl='pallas'.
  * ``impl='native'`` — plain f32 dot at ``Precision.HIGHEST`` (numerically
        ~= M24 on every backend); used for fast CPU end-to-end examples.

High modes (M32/M48) accumulate their partial products with Neumaier
compensation over K-chunks, because a plain f32 accumulator would cap the
achievable precision near 2^-24 for large K (see DESIGN.md section 2 / tests).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import limb as limb_lib
from repro.core.precision import (
    DF32_MODES,
    F32_MODES,
    MODE_LIMBS,
    DoubleF32,
    Mode,
    auto_mode,
)

Array = jax.Array


def _two_sum(a: Array, b: Array) -> tuple[Array, Array]:
    """Knuth TwoSum: s + e == a + b exactly (s = fl(a+b))."""
    s = a + b
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def _neumaier_sum(parts: Sequence[Array]) -> Array:
    s = parts[0]
    comp = jnp.zeros_like(s)
    for p in parts[1:]:
        s, e = _two_sum(s, p)
        comp = comp + e
    return s + comp


# ---------------------------------------------------------------------------
# Core limb contraction
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class OneLimbStats:
    """Trace-time count of contraction sites that took the one-limb path:
    ``einsums_saved`` is einsum dispatches not emitted (k(k+1)/2 - 1 per
    site), ``passes_saved`` the partial products whose limb is zero and
    which were therefore never multiplied."""

    sites: int = 0
    einsums_saved: int = 0
    passes_saved: int = 0

    def since(self, snap: "OneLimbStats") -> "OneLimbStats":
        return OneLimbStats(*(getattr(self, f.name) - getattr(snap, f.name)
                              for f in dataclasses.fields(self)))


_ONE_LIMB = OneLimbStats()


def one_limb_stats() -> OneLimbStats:
    """The running one-limb count (copy it to take a snapshot)."""
    return _ONE_LIMB


def _is_bf16(x) -> bool:
    return not isinstance(x, DoubleF32) and x.dtype == jnp.bfloat16


def _one_limb_einsum(eq: str, a, b, k: int, rounding: str) -> Array:
    """The k-limb contraction when an operand is ``bfloat16``: that operand
    is its own single exact limb (its split is ``[x, 0, ...]``), so only the
    other operand is split, its limbs stacked on a fresh leading index and
    contracted in ONE einsum, and the partial results summed highest limb
    first — the order the retained terms with a nonzero limb have in
    ``_limb_einsum``.  Both operands bf16: one plain pass."""
    n_terms = len(limb_lib.limb_product_terms(k))
    _ONE_LIMB.sites += 1
    _ONE_LIMB.einsums_saved += n_terms - 1
    if _is_bf16(a) and _is_bf16(b):
        _ONE_LIMB.passes_saved += n_terms - 1
        return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)
    _ONE_LIMB.passes_saved += n_terms - k
    z = next(c for c in "zyxwvutsrqponmlkjihgfedcba" if c not in eq)
    lhs, out = eq.split("->")
    ea, eb = lhs.split(",")
    if _is_bf16(b):
        stacked_eq = f"{z}{ea},{eb}->{z}{out}"
        a = limb_lib.split_limbs(a, k, rounding)
    else:
        stacked_eq = f"{ea},{z}{eb}->{z}{out}"
        b = limb_lib.split_limbs(b, k, rounding)
    parts = jnp.einsum(stacked_eq, a, b, preferred_element_type=jnp.float32)
    acc = parts[k - 1]
    for i in range(k - 2, -1, -1):
        acc = acc + parts[i]
    return acc


def _limb_einsum(
    eq: str,
    a,
    b,
    k: int,
    rounding: str = "rne",
    compensated: bool | None = None,
) -> Array:
    """k-limb multi-pass contraction: sum over Karatsuba terms (i+j < k) of
    einsum(a_i, b_j), bf16 x bf16 -> f32 per pass.  A ``bfloat16`` operand
    takes the one-limb path (:func:`_one_limb_einsum`) on the plain f32
    ladder; the compensated modes keep the full split."""
    if compensated is None:
        compensated = k >= 4
    if not compensated and (_is_bf16(a) or _is_bf16(b)):
        return _one_limb_einsum(eq, a, b, k, rounding)
    a_limbs = limb_lib.split_limbs(a, k, rounding)
    b_limbs = limb_lib.split_limbs(b, k, rounding)
    terms = limb_lib.limb_product_terms(k)
    parts = [
        jnp.einsum(eq, a_limbs[i], b_limbs[j], preferred_element_type=jnp.float32)
        for (i, j) in terms
    ]
    if compensated:
        return _neumaier_sum(parts)
    acc = parts[0]
    for p in parts[1:]:
        acc = acc + p
    return acc


def _limb_matmul_dd(a, b, k: int, rounding: str = "rne") -> Array:
    """High-precision (M32/M48) 2D matmul with double-f32 (Neumaier)
    accumulation.  a: (M, K), b: (K, N)  ->  (M, N) f32.

    bf16 x bf16 elementwise products are EXACT in f32 (16-bit significands),
    so the only error source is summation; a TwoSum cascade over the K axis
    and the Karatsuba terms keeps it near u^2 ~ 2^-48 instead of the ~2^-24
    cap of a monolithic f32 dot.  O(K) sequential — this is the
    validation-grade path; the TPU Pallas kernel carries (sum, comp) f32
    accumulator pairs in VMEM across K-tiles for the same effect per tile.
    """
    a_limbs = limb_lib.split_limbs(a, k, rounding)  # (k, M, K)
    b_limbs = limb_lib.split_limbs(b, k, rounding)  # (k, K, N)
    terms = limb_lib.limb_product_terms(k)
    m, kdim = a_limbs.shape[1], a_limbs.shape[2]
    n = b_limbs.shape[2]
    a_f = a_limbs.astype(jnp.float32)
    b_f = b_limbs.astype(jnp.float32)

    def step(carry, x):
        s, comp = carry
        for i, j in terms:
            p = a_f[i, :, x][:, None] * b_f[j, x, :][None, :]  # exact in f32
            s, e = _two_sum(s, p)
            comp = comp + e
        return (s, comp), None

    zeros = jnp.zeros((m, n), jnp.float32)
    (s, comp), _ = jax.lax.scan(step, (zeros, zeros), jnp.arange(kdim))
    # The result carries > 24 significand bits, so it is returned as a
    # DoubleF32 pair (the paper likewise outputs the full double-width word).
    hi, lo = _two_sum(s, comp)
    return DoubleF32(hi, lo)


def _check_mode_operands(mode: Mode, a, b) -> None:
    if mode in DF32_MODES:
        return  # DoubleF32 preferred but plain f32 accepted (lo = 0)
    if isinstance(a, DoubleF32) or isinstance(b, DoubleF32):
        raise ValueError(
            f"mode {mode.name} is an f32 mode; DoubleF32 operands need M32/M48"
        )


def mp_einsum(
    eq: str,
    a,
    b,
    mode: Mode = Mode.M24,
    *,
    rounding: str = "rne",
    impl: str = "xla",
    block: tuple[int, int, int] | None = None,
) -> Array:
    """Multi-precision einsum through the RMPM engine (two-operand).

    ``block`` carries the autotuner's Pallas (bm, bn, bk) tile override: it
    is honoured when ``impl='pallas'`` and ``eq`` is the plain 2D matmul
    contraction (dispatched to the fused kernel), and ignored otherwise —
    general einsum contractions run the XLA limb algebra, whose tiling XLA
    owns (same contract as ``mp_matmul``).
    """
    mode = Mode(mode)
    if impl in ("pallas", "tile") and eq == "mk,kn->mn" and mode != Mode.AUTO:
        return mp_matmul(a, b, mode, rounding=rounding, impl=impl, block=block)
    if impl == "native" or mode == Mode.AUTO:
        if mode == Mode.AUTO:
            raise ValueError("AUTO requires mp_matmul_runtime / mp_einsum_runtime")
        av = a.hi + a.lo if isinstance(a, DoubleF32) else a
        bv = b.hi + b.lo if isinstance(b, DoubleF32) else b
        # HIGHEST keeps 'native' at f32 fidelity on every backend: a
        # default-precision f32 dot is one bf16 pass (~M8) on the TPU
        return jnp.einsum(
            eq,
            av.astype(jnp.float32),
            bv.astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    _check_mode_operands(mode, a, b)
    return _limb_einsum(eq, a, b, MODE_LIMBS[mode], rounding)


def mp_matmul(
    a,
    b,
    mode: Mode = Mode.M24,
    *,
    rounding: str = "rne",
    impl: str = "xla",
    strassen_depth: int = 0,
    block: tuple[int, int, int] | None = None,
) -> Array:
    """Multi-precision matmul: a (..., K) @ b (K, N) -> (..., N) f32.

    ``strassen_depth > 0`` routes through the paper's top-down Strassen block
    recursion (C4) with this engine at the leaves.  ``block`` overrides the
    Pallas kernel's (bm, bn, bk) tile sizes — the autotuner's fourth lever
    (repro.tune); it is ignored by the non-Pallas impls, whose tiling XLA
    owns.
    """
    mode = Mode(mode)
    if strassen_depth > 0:
        from repro.core import strassen as strassen_lib  # local import (cycle)

        leaf = functools.partial(
            mp_matmul, mode=mode, rounding=rounding, impl=impl, block=block
        )
        return strassen_lib.strassen_matmul(a, b, depth=strassen_depth, leaf_fn=leaf)
    if impl == "tile":
        from repro.kernels.tile_matmul import ops as tile_ops

        bm, bn, bk = block if block is not None else tile_ops.DEFAULT_BLOCK
        return tile_ops.tile_matmul_mode(
            a, b, mode, rounding=rounding, bm=bm, bn=bn, bk=bk
        )
    if impl == "pallas":
        from repro.kernels.limb_matmul import ops as limb_ops

        if block is not None:
            bm, bn, bk = block
            return limb_ops.limb_matmul(
                a, b, MODE_LIMBS[mode], rounding=rounding, bm=bm, bn=bn, bk=bk
            )
        return limb_ops.limb_matmul(a, b, MODE_LIMBS[mode], rounding=rounding)
    shape_a = a.hi.shape if isinstance(a, DoubleF32) else a.shape
    if len(shape_a) == 2:
        if mode in DF32_MODES and impl == "xla":
            return _limb_matmul_dd(a, b, MODE_LIMBS[mode], rounding)
        return mp_einsum("mk,kn->mn", a, b, mode, rounding=rounding, impl=impl)
    # Rank-generic einsum — do NOT flatten leading dims: a (batch, seq, d)
    # reshape would merge two differently-sharded dims and GSPMD falls back
    # to replicating the matmul over 'model' (measured 16x HLO-flop waste on
    # sequence-parallel archs; EXPERIMENTS.md section Perf cell A).
    lead = "uvwxyz"[: len(shape_a) - 1]
    eq = f"{lead}k,kn->{lead}n"
    return mp_einsum(eq, a, b, mode, rounding=rounding, impl=impl)


# ---------------------------------------------------------------------------
# Runtime reconfiguration (C1's mode-select bits + C2's auto-mode)
# ---------------------------------------------------------------------------


def mp_matmul_runtime(
    a: Array,
    b: Array,
    mode: Array | int | Mode = Mode.AUTO,
    *,
    rounding: str = "rne",
    auto_tol: float = 0.0,
    impl: str = "xla",
    block: tuple[int, int, int] | None = None,
    allow_auto: bool = True,
) -> Array:
    """Runtime-reconfigurable matmul over the f32 mode set {M8, M16, M24}.

    ``mode`` may be a traced int32 scalar (the paper's mode-select bits) — the
    executable contains all three branches but only the selected one runs.
    ``Mode.AUTO`` (0) probes operands and picks the cheapest adequate mode.

    ``impl``/``block`` plumb the planner's execution choice and the
    autotuner's Pallas tile override into every branch, so an adapted
    call-site (repro.adapt) keeps its tuned blocks when the mode scalar
    changes — the tile shape is a property of the GEMM geometry, not of the
    limb count.

    ``allow_auto=False`` asserts the scalar is a concrete mode (1..3), never
    ``Mode.AUTO``: the operand-occupancy probe is skipped entirely.  The
    probe costs a full read of both operands (3 rounds of casts +
    reductions), and ``jnp.where`` evaluates it even when the scalar is
    never 0 — for memory-bound GEMMs that multiplies the step cost.  The
    adaptation loop (repro.adapt), whose mode tables only hold concrete
    modes, uses this path.
    """
    if isinstance(mode, Mode) and mode != Mode.AUTO:
        return mp_matmul(a, b, mode, rounding=rounding, impl=impl, block=block)
    mode_scalar = jnp.asarray(mode, jnp.int32)
    if allow_auto:
        selected = jnp.where(
            mode_scalar == int(Mode.AUTO),
            auto_mode(a, b, tol=auto_tol, max_mode=Mode.M24),
            mode_scalar,
        )
    else:
        selected = mode_scalar
    if impl == "tile":
        # Partitioned-SIMD path: ONE fused dispatch for every mode — the
        # traced scalar becomes a uniform tile map inside the kernel instead
        # of selecting one of N branch executables.
        from repro.kernels.tile_matmul import ops as tile_ops

        bm, bn, bk = block if block is not None else tile_ops.DEFAULT_BLOCK
        return tile_ops.tile_matmul_runtime(
            a, b, selected, rounding=rounding, bm=bm, bn=bn, bk=bk
        )
    branches = [
        functools.partial(mp_matmul, mode=m, rounding=rounding, impl=impl,
                          block=block)
        for m in F32_MODES
    ]
    return jax.lax.switch(jnp.clip(selected - 1, 0, len(branches) - 1), branches, a, b)


def mp_einsum_runtime(
    eq: str,
    a: Array,
    b: Array,
    mode: Array | int,
    *,
    rounding: str = "rne",
    impl: str = "xla",
    block: tuple[int, int, int] | None = None,
) -> Array:
    """Runtime-switchable einsum over the f32 mode set {M8, M16, M24} —
    ``mp_matmul_runtime``'s contraction-generic sibling, used by the adapted
    ``pein`` call-sites (attention scores / attention-value).

    ``impl``/``block`` are forwarded to every branch under the same contract
    as :func:`mp_einsum` (``block`` only takes effect for the pallas 2D
    matmul dispatch).  ``impl='native'`` is rejected: its branches would all
    compute the same plain f32 einsum, silently turning the mode switch into
    a no-op — callers wanting native execution should not bind the site.
    """
    if impl == "native":
        raise ValueError(
            "impl='native' ignores the mode: a runtime switch over identical "
            "branches is a no-op; use the static mp_einsum instead"
        )
    mode_scalar = jnp.asarray(mode, jnp.int32)
    if impl == "tile":
        if eq == "mk,kn->mn":
            return mp_matmul_runtime(
                a, b, mode_scalar, rounding=rounding, impl="tile", block=block,
                allow_auto=False,
            )
        # General contractions have no tile kernel; keep the switch over the
        # XLA limb algebra rather than silently changing numerics.
        impl = "xla"
    branches = [
        functools.partial(mp_einsum, eq, mode=m, rounding=rounding, impl=impl,
                          block=block)
        for m in F32_MODES
    ]
    return jax.lax.switch(
        jnp.clip(mode_scalar - 1, 0, len(branches) - 1), branches, a, b
    )


def mp_matmul_runtime_df32(
    a: DoubleF32,
    b: DoubleF32,
    mode: Array | int | Mode,
    *,
    rounding: str = "rne",
) -> Array:
    """Runtime switch over the extended-precision mode set {M32, M48}."""
    mode_scalar = jnp.asarray(mode, jnp.int32)
    branches = [
        functools.partial(mp_matmul, mode=m, rounding=rounding) for m in DF32_MODES
    ]
    idx = jnp.clip(mode_scalar - int(Mode.M32), 0, len(branches) - 1)
    return jax.lax.switch(idx, branches, a, b)


# ---------------------------------------------------------------------------
# Model-facing convenience
# ---------------------------------------------------------------------------


def mp_linear(x: Array, w: Array, b: Array | None, mode: Mode, **kw) -> Array:
    out = mp_matmul(x, w, mode, **kw)
    if b is not None:
        out = out + b.astype(out.dtype)
    return out
