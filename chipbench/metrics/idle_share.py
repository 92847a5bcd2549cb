"""Device: share of the traced window in which no operation ran on the
device (1 minus the union of busy intervals over the window), %."""
from __future__ import annotations


def read(ctx, suffix):
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
