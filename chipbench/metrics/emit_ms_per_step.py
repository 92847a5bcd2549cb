"""Engine / scheduler: host time after a decode step's sync, before the next
step's work, ms per decode step: the engine's ``serve.emit`` span (each row's
token bookkeeping and completions), summed over the traced window, over its
decode steps."""
from __future__ import annotations

from chipbench import scopes


def read(ctx, suffix):
    return scopes.of(ctx).ms_per_decode_step(("emit",), ctx.trace)
