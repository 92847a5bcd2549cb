"""Model step: device time per decode step (the engine's jitted masked
step, by its name in the trace), mean over the traced window, ms."""
from __future__ import annotations

from chipbench.readers import DECODE_PROGRAM


def read(ctx, suffix):
    spans = ctx.trace.device_spans("modules", DECODE_PROGRAM)
    return sum(s.dur for s in spans) / len(spans) * 1e-6 if spans else None
