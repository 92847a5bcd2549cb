"""Engine and scheduler, seen from the client: time to first token of
every request due in the window, from when it was due, 95th percentile,
ms.  Host clock, the whole window; a request with no first token counts at
the time it had waited (``Drive.ttft``)."""
from __future__ import annotations

import numpy as np


def read(ctx, suffix):
    waits, _ = ctx.drive.ttft()
    return float(np.percentile(waits, 95) * 1e3) if waits else None
