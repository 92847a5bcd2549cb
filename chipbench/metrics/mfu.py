"""Whole step: model FLOPs of the work the engine finished in the traced
window (every decode token and every prefill, ``chipbench.workcount``),
per second of the window, over the chip's bf16 peak, %."""
from __future__ import annotations


def read(ctx, suffix):
    flops = 0
    for step, _, _ in ctx.traced_steps():
        flops += ctx.dims.decode_flops(step.contexts) if step.contexts else 0
        flops += sum(ctx.dims.prefill_flops(p) for p in step.prefills)
    if not flops:
        return None
    return 100.0 * flops / ctx.trace.window_s / ctx.peaks.bf16_flops
