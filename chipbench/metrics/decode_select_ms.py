"""Masked step merge: device time of the decode step's operations charged
to ``select`` (the per-row select of each state leaf's new rows against the
old), per decode step (the engine's jitted masked step), mean over the
traced window, ms.  The charge rule: ``chipbench.scopes``."""
from __future__ import annotations

from chipbench import scopes


def read(ctx, suffix):
    return scopes.of(ctx).decode_scope_ms(ctx.trace).get("select")
