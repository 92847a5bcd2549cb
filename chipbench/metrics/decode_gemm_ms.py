"""Limb GEMMs: device time of the decode step's operations charged to
``gemm`` (the weight GEMMs and the head, each under ``gemm/<op>``), per
decode step (the engine's jitted masked step), mean over the traced window,
ms.  The charge rule: ``chipbench.scopes``."""
from __future__ import annotations

from chipbench import scopes


def read(ctx, suffix):
    return scopes.of(ctx).decode_scope_ms(ctx.trace).get("gemm")
