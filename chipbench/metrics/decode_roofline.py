"""Model step: share of the roofline of the decode steps in the traced
window, %: the least time of each step's work at the ask (its GEMMs at
2*M*K*N with M the rows decoding; weights at the storage the decode modes
allow plus the live cache or state; ``chipbench.workcount``), summed, over
the device time of the decode programs in those steps."""
from __future__ import annotations

from chipbench.readers import DECODE_PROGRAM, spans_within
from chipbench.workcount import least_seconds


def read(ctx, suffix):
    spans = ctx.trace.device_spans("modules", DECODE_PROGRAM)
    least = device = 0.0
    for step, a, b in ctx.traced_steps():
        inside = spans_within(spans, a, b)
        if not step.contexts or not inside:
            continue
        mode = ctx.decode_mode.__getitem__
        t, _ = least_seconds(ctx.dims.decode_flops(step.contexts),
                             ctx.dims.decode_bytes(step.contexts, mode), ctx.peaks)
        least += t
        device += sum(s.dur for s in inside) * 1e-9
    return 100.0 * least / device if device else None
