"""SwiGLU MLP: gate and up projections of (d_model, d_ff), then down."""
from __future__ import annotations


def gemms(d):
    return {"mlp_up": [(d.d_model, d.d_ff)] * 2, "mlp_down": [(d.d_ff, d.d_model)]}
