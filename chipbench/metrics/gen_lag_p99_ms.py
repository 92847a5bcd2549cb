"""Load generator: how late the client submitted each request due in the
window (submission time minus due time), 99th percentile, ms.  Host clock,
the whole window."""
from __future__ import annotations

import numpy as np

from chipbench.traffic import WINDOW


def read(ctx, suffix):
    lags = [r.submit_t - r.due_t for r in ctx.drive.records.values()
            if r.arrival.segment == WINDOW and r.due_t is not None]
    return float(np.percentile(lags, 99) * 1e3) if lags else None
