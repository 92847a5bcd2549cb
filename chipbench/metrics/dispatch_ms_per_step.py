"""Engine / scheduler: host time before the device can start a decode step,
ms per decode step: the engine's ``serve.decode.inputs`` (tokens, mask and
mode scalars to the device) and ``serve.decode.dispatch`` (until the jitted
call returns) spans, summed over the traced window, over its decode steps."""
from __future__ import annotations

from chipbench import scopes


def read(ctx, suffix):
    return scopes.of(ctx).ms_per_decode_step(("decode.inputs", "decode.dispatch"), ctx.trace)
