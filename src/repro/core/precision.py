"""Precision modes for the run-time-reconfigurable multi-precision matmul.

Paper mapping (Arish & Sharma 2017, Table 1):

    paper mode 1 (000) auto            -> Mode.AUTO   (operand probe, C2)
    paper mode 2 (001) 8-bit mantissa  -> Mode.M8     (1 bf16 limb,  1 pass)
    paper mode 3 (010) 16-bit          -> Mode.M16    (2 limbs,      3 passes)
    paper mode 4 (011) 23-bit (single) -> Mode.M24    (3 limbs,      6 passes)
    paper mode 5 (100) 36-bit          -> Mode.M32    (4 limbs,     10 passes)
    paper mode 6 (101) 52-bit (double) -> Mode.M48    (6 limbs,     21 passes)

The TPU MXU's native multiply quantum is the bf16 8-bit significand, so the
paper's mantissa ladder is re-quantized to limb multiples (DESIGN.md section 2).
Modes >= M32 require DoubleF32 (hi, lo) operands since TPU has no f64.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.rounding import quantize_mantissa


class Mode(enum.IntEnum):
    """Precision mode select (the paper's 3 mode-select bits)."""

    AUTO = 0
    M8 = 1
    M16 = 2
    M24 = 3
    M32 = 4
    M48 = 5


# Number of bf16 limbs per mode.
MODE_LIMBS: dict[Mode, int] = {
    Mode.M8: 1,
    Mode.M16: 2,
    Mode.M24: 3,
    Mode.M32: 4,
    Mode.M48: 6,
}

# Effective significand bits carried per mode (8 bits per limb; bf16 has a
# 7-bit explicit + 1 hidden significand).
MODE_BITS: dict[Mode, int] = {m: 8 * k for m, k in MODE_LIMBS.items()}

# MXU passes = number of retained Karatsuba cross products: |{(i,j): i+j<k}|.
MODE_PASSES: dict[Mode, int] = {m: k * (k + 1) // 2 for m, k in MODE_LIMBS.items()}

# Modes that operate on plain f32 operands (runtime-switchable set).
F32_MODES = (Mode.M8, Mode.M16, Mode.M24)
# Modes that require DoubleF32 operands.
DF32_MODES = (Mode.M32, Mode.M48)


class DoubleF32(NamedTuple):
    """Unevaluated hi+lo f32 pair (Dekker / double-double style).

    value == hi + lo with |lo| <= ulp(hi)/2.  This is the TPU-side stand-in
    for the paper's 52-bit-mantissa double-precision operands.
    """

    hi: jax.Array
    lo: jax.Array

    @property
    def shape(self):
        return self.hi.shape

    @property
    def dtype(self):
        return self.hi.dtype

    def value_f64(self) -> jax.Array:  # oracle-side only (requires x64)
        return self.hi.astype(jnp.float64) + self.lo.astype(jnp.float64)


def df32_from_f64(x) -> DoubleF32:
    """Split a float64 array into a DoubleF32 pair (test/oracle helper)."""
    hi = x.astype(jnp.float32)
    lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
    return DoubleF32(jnp.asarray(hi), jnp.asarray(lo))


def df32_from_f32(x: jax.Array) -> DoubleF32:
    return DoubleF32(x.astype(jnp.float32), jnp.zeros_like(x, jnp.float32))


@dataclasses.dataclass(frozen=True)
class PrecisionSpec:
    """Carried alongside operands; the software analogue of the paper's
    67-bit bus format (3 mode bits prepended to the IEEE word)."""

    mode: Mode = Mode.M24
    rounding: str = "rne"  # 'rne' | 'grte' | 'trunc'  (C3)
    auto_tol: float = 0.0  # relative tolerance for auto-mode probe

    @property
    def limbs(self) -> int:
        return MODE_LIMBS[self.mode]


def classify(x: jax.Array) -> dict[str, jax.Array]:
    """Exception signals of the paper's multiplier output port:
    zero / infinity / NaN / denormal (per-element booleans).

    Bit-level (exponent==0 / all-ones) so flush-to-zero backends cannot hide
    denormals — mirrors the paper's exponent+significand field tests.
    """
    if x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    xi = jax.lax.bitcast_convert_type(x, jnp.uint32)
    exp = (xi >> 23) & jnp.uint32(0xFF)
    mant = xi & jnp.uint32(0x7FFFFF)
    return {
        "zero": (exp == 0) & (mant == 0),
        "infinity": (exp == 0xFF) & (mant == 0),
        "nan": (exp == 0xFF) & (mant != 0),
        "denormal": (exp == 0) & (mant != 0),
    }


def mode_mismatch_error(mode_a: jax.Array, mode_b: jax.Array) -> jax.Array:
    """Paper section 3.3.1: operands carrying different mode-select bits raise the
    mode-select-error signal."""
    return jnp.asarray(mode_a) != jnp.asarray(mode_b)


# ---------------------------------------------------------------------------
# Auto-mode (C2): operand limb-occupancy probe.
# ---------------------------------------------------------------------------


def _limbs_needed(x: jax.Array, max_limbs: int, tol: float) -> jax.Array:
    """Smallest k such that the k-limb bf16 expansion reconstructs ``x`` to
    within ``tol * max|x|``.  TPU analogue of the paper's trailing-zero count
    (Fig 7): integer-valued / low-precision data needs fewer limbs."""
    r = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(r)), jnp.finfo(jnp.float32).tiny)
    # Residual magnitude decreases with each extracted limb, so the first k
    # whose residual is within tolerance is  max_limbs - (#ok levels) + 1.
    n_ok = jnp.int32(0)
    for _ in range(max_limbs):
        # integer-domain rounding: a bare bf16 round trip may be elided on
        # the TPU (see core.limb.split_limbs), which would zero the residual
        limb = quantize_mantissa(r, 7, "rne")
        r = r - limb
        ok = jnp.max(jnp.abs(r)) <= tol * scale
        n_ok = n_ok + ok.astype(jnp.int32)
    return jnp.clip(jnp.int32(max_limbs) - n_ok + 1, 1, max_limbs)


def auto_mode(a: jax.Array, b: jax.Array, tol: float = 0.0, max_mode: Mode = Mode.M24) -> jax.Array:
    """Runtime mode selection from operand contents (paper mode 1).

    Returns an int32 scalar in {1..max_mode} suitable for ``lax.switch``
    dispatch inside a jitted computation (no recompilation — the FPGA paper's
    'no re-synthesis' property).
    """
    max_limbs = MODE_LIMBS[Mode(max_mode)]
    ka = _limbs_needed(a, max_limbs, tol)
    kb = _limbs_needed(b, max_limbs, tol)
    k = jnp.maximum(ka, kb)
    # limb count -> mode index (1,2,3 limbs -> M8,M16,M24; 4->M32; 6->M48)
    k_to_mode = jnp.array([0, 1, 2, 3, 4, 5, 5], dtype=jnp.int32)
    return k_to_mode[jnp.clip(k, 1, 6)]
