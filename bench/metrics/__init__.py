"""One reader per per-layer metric, named as the metric.  ``read(ctx)``
returns the value, or ``None`` where the run holds nothing to read."""
