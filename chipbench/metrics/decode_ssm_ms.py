"""SSM mixer: device time of the decode step's operations charged to
``ssm`` (the Mamba2 conv, SSD recurrence, state update and gated norm; not
its projections), per decode step (the engine's jitted masked step), mean
over the traced window, ms.  The charge rule: ``chipbench.scopes``."""
from __future__ import annotations

from chipbench import scopes


def read(ctx, suffix):
    return scopes.of(ctx).decode_scope_ms(ctx.trace).get("ssm")
