"""Model step: device time per prefill call (the engine's jitted prefill
program, by its name in the trace), mean over the traced window, ms."""
from __future__ import annotations

from chipbench.readers import PREFILL_PROGRAM


def read(ctx, suffix):
    spans = ctx.trace.device_spans("modules", PREFILL_PROGRAM)
    return sum(s.dur for s in spans) / len(spans) * 1e-6 if spans else None
