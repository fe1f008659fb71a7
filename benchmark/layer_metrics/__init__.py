"""One reader per per-layer metric: `read(ctx) -> float | None`."""
