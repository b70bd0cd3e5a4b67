"""One file per per-layer metric: ``read(ctx)`` returns the number, or None
where this run holds nothing to read it from. Found by the metric's name."""
