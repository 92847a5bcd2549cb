"""Engine / scheduler: from when a request was due to the engine tracer's
``admit`` event for it (the start of its prefill), 95th percentile, ms.
Host clock, every request due in the window that was admitted."""
from __future__ import annotations

import numpy as np

from chipbench.traffic import WINDOW


def read(ctx, suffix):
    waits = [ctx.admits[rid] - r.due_t for rid, r in ctx.drive.records.items()
             if r.arrival.segment == WINDOW and r.due_t is not None and rid in ctx.admits]
    return float(np.percentile(waits, 95) * 1e3) if waits else None
