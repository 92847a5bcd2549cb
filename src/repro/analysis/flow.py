"""Precision-flow checker: abstract interpretation over jaxprs.

The lattice value of a float variable is its *effective mantissa width* in
bits — ``float64`` 53, ``float32`` 24, ``float16`` 11, ``bfloat16`` 8.  A
``quantize_mantissa`` site lowers the value (keeps the storage dtype but
truncates mantissa content); a ``convert_element_type`` moves it between
storage widths.  Walking the traced jaxpr of a hot path, four contracts
from the paper's run-time-reconfigurable datapath are checked:

``FLOW-F64``
    No float64 value — invar, constvar, or equation output — may appear in
    a device path, except inside declared oracle sub-jaxprs.  Traces run
    under ``jax.enable_x64`` so a latent f64 cannot hide
    behind jax's silent default-config downcast.  Weak-typed scalars
    (plain Python floats awaiting promotion) are exempt.

``FLOW-WIDEN``
    Every ``convert_element_type`` that *widens* a float must be on an
    allowlisted accumulation edge (default: ``bfloat16 -> float32``, the
    limb-accumulation contract).  Anything else is a silent upcast that
    would mask the configured precision.

``FLOW-MODE``
    Mode-select arguments must reach the jaxpr as traced int32 scalars
    AND be consumed by at least one equation.  An unused mode invar means
    the Python body constant-folded the mode — the zero-recompile contract
    is broken (each mode would recompile).

``FLOW-NARROW``
    ``quantize_mantissa`` / limb-truncation sites (pjit equations whose
    name contains ``quantize_mantissa``) may only *narrow* the lattice
    value: output storage bits must not exceed the input's lattice bits.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.analysis.report import Violation

#: mantissa bits (incl. implicit leading 1) per float storage dtype
MANTISSA_BITS = {
    "float64": 53,
    "float32": 24,
    "float16": 11,
    "bfloat16": 8,
}

#: float widenings that are part of the datapath contract (limb products
#: accumulate in f32; everything else must justify itself per-path)
DEFAULT_WIDEN_ALLOW = (("bfloat16", "float32"),)


def _aval(var):
    return getattr(var, "aval", None)


def _dtype_name(aval) -> str | None:
    dt = getattr(aval, "dtype", None)
    return None if dt is None else str(dt)


def _float_bits(aval) -> int | None:
    """Storage mantissa bits if ``aval`` is a float, else None."""
    name = _dtype_name(aval)
    return None if name is None else MANTISSA_BITS.get(name)


def _is_weak(aval) -> bool:
    return bool(getattr(aval, "weak_type", False))


def _is_literal(var) -> bool:
    return hasattr(var, "val")


def analyze_flow(fn, *args, path: str,
                 mode_args: tuple[int, ...] = (),
                 widen_allow=DEFAULT_WIDEN_ALLOW,
                 oracles: tuple[str, ...] = (),
                 x64: bool = True,
                 **kwargs) -> list[Violation]:
    """Trace ``fn(*args, **kwargs)`` and run all four flow rules.

    ``mode_args`` are positional indices (into ``args``) of mode-select
    scalars; ``oracles`` are substrings of nested-jaxpr names whose bodies
    are declared f64-capable (reference oracles) and skipped; ``x64``
    traces under ``jax.enable_x64`` so strong float64 cannot be masked.
    """
    def trace():
        return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)

    if x64:
        with jax.enable_x64(True):
            closed = trace()
    else:
        closed = trace()
    mode_offsets = _mode_offsets(args, mode_args)
    return flow_violations(closed, path, mode_offsets=mode_offsets,
                           widen_allow=widen_allow, oracles=oracles)


def _mode_offsets(args, mode_args: tuple[int, ...]):
    """Map positional arg indices to groups of flattened-invar offsets.

    One group per declared mode argument: a mode arg may be a single
    scalar or a pytree of per-site scalars (a ModeTable ``scalars()``
    dict).  Every leaf must be int32, but only the *argument* must be
    consumed (≥ 1 leaf read) — site scalars unused by an architecture are
    merely inert args, not constant-folded modes.
    """
    counts = [len(jax.tree_util.tree_leaves(a)) for a in args]
    starts = np.concatenate([[0], np.cumsum(counts)]).tolist()
    return tuple(
        (idx, tuple(range(starts[idx], starts[idx] + counts[idx])))
        for idx in mode_args)


def flow_violations(closed, path: str, *,
                    mode_offsets=(),  # ((arg_idx, (invar offsets...)), ...)
                    widen_allow=DEFAULT_WIDEN_ALLOW,
                    oracles: tuple[str, ...] = ()) -> list[Violation]:
    """All four flow rules over an already-traced ClosedJaxpr."""
    out: list[Violation] = []
    allow = {tuple(pair) for pair in widen_allow}
    jaxpr = closed.jaxpr
    seen_f64: set[str] = set()

    # lattice env: id(var) -> effective mantissa bits (floats only)
    env: dict[int, int] = {}

    def bits_of(var) -> int | None:
        aval = _aval(var)
        b = _float_bits(aval)
        if b is None:
            return None
        return env.get(id(var), b)

    def note_f64(aval, what: str) -> None:
        if _dtype_name(aval) == "float64" and not _is_weak(aval):
            if what not in seen_f64:
                seen_f64.add(what)
                out.append(Violation(
                    "FLOW-F64", path,
                    f"float64 on device path at {what} "
                    "(declare an oracle or narrow the source)"))

    def seed(var) -> None:
        aval = _aval(var)
        b = _float_bits(aval)
        if b is not None:
            env.setdefault(id(var), b)

    def walk(jpr, depth: int) -> None:
        for var in list(jpr.invars) + list(jpr.constvars):
            note_f64(_aval(var), f"depth{depth} invar {var}")
            seed(var)
        for eqn in jpr.eqns:
            prim = eqn.primitive.name
            if prim == "pallas_call":
                # kernel bodies run the predicated datapath; their refs
                # are not host-visible dtypes — audit outputs only
                for ov in eqn.outvars:
                    note_f64(_aval(ov), f"{prim} out {ov}")
                    seed(ov)
                continue
            name = str(eqn.params.get("name", "")) if eqn.params else ""
            if name and any(tag in name for tag in oracles):
                for ov in eqn.outvars:
                    seed(ov)
                continue  # declared f64 oracle: body exempt
            in_bits = [b for b in (bits_of(v) for v in eqn.invars)
                       if b is not None]
            if prim == "convert_element_type":
                src = _aval(eqn.invars[0])
                dst = _aval(eqn.outvars[0])
                sb, db = _float_bits(src), _float_bits(dst)
                if (sb is not None and db is not None and db > sb
                        and not _is_weak(src)
                        and (_dtype_name(src), _dtype_name(dst)) not in allow):
                    out.append(Violation(
                        "FLOW-WIDEN", path,
                        f"un-allowlisted float widening "
                        f"{_dtype_name(src)} -> {_dtype_name(dst)}"))
            if "quantize_mantissa" in name:
                for ov in eqn.outvars:
                    ob = _float_bits(_aval(ov))
                    if ob is None:
                        continue
                    src_bits = max(in_bits) if in_bits else ob
                    if ob > src_bits:
                        out.append(Violation(
                            "FLOW-NARROW", path,
                            f"quantize site '{name}' widens the lattice: "
                            f"{src_bits} -> {ob} mantissa bits"))
                    env[id(ov)] = min(ob, src_bits)
            for ov in eqn.outvars:
                note_f64(_aval(ov), f"{prim} out {ov}")
                seed(ov)
            for sub in _subjaxprs(eqn.params):
                walk(sub, depth + 1)

    walk(jaxpr, 0)

    # FLOW-MODE: each declared mode invar must be int32 and consumed
    used: set[int] = set()
    def mark_used(jpr) -> None:
        for eqn in jpr.eqns:
            for v in eqn.invars:
                if not _is_literal(v):
                    used.add(id(v))
        for v in jpr.outvars:
            if not _is_literal(v):
                used.add(id(v))
    mark_used(jaxpr)
    for arg_idx, offsets in mode_offsets:
        consumed = False
        for off in offsets:
            var = jaxpr.invars[off]
            name = _dtype_name(_aval(var))
            if name != "int32":
                out.append(Violation(
                    "FLOW-MODE", path,
                    f"mode arg {arg_idx} (invar {off}) has dtype {name}, "
                    "must be traced int32"))
            consumed = consumed or id(var) in used
        if offsets and not consumed:
            out.append(Violation(
                "FLOW-MODE", path,
                f"mode arg {arg_idx} is never consumed — the mode was "
                "constant-folded in Python, breaking the zero-recompile "
                "contract"))
    return out


def _subjaxprs(params):
    """Nested jaxprs, duck-typed (shared shape with dispatch._subjaxprs
    but kept local so flow has no import edge on dispatch)."""
    if not params:
        return
    for val in params.values():
        for item in val if isinstance(val, (tuple, list)) else (val,):
            if hasattr(item, "jaxpr") and hasattr(getattr(item, "jaxpr"), "eqns"):
                yield item.jaxpr  # ClosedJaxpr (unwrap before the eqns probe:
                #                   ClosedJaxpr forwards .eqns but not .invars)
            elif hasattr(item, "eqns"):
                yield item
