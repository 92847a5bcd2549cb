"""Per-layer metric readers, one file per metric base name.  Each exposes
``read(ctx, suffix) -> float | None`` (``ctx``: ``chipbench.readers.Context``;
``suffix``: the end-to-end metric the metric moves, after the dot)."""
