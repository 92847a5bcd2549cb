"""Engine / scheduler: host time per engine step, ms: the wall time of each
``ServeEngine.step`` call (the benchmark's ``chipbench.step`` span) minus the
device-busy time inside it, averaged over the steps of the traced window."""
from __future__ import annotations


def read(ctx, suffix):
    steps = ctx.traced_steps()
    if not steps:
        return None
    host = [(b - a) * 1e-9 - ctx.trace.busy_between(a, b) for _, a, b in steps]
    return sum(host) / len(host) * 1e3
