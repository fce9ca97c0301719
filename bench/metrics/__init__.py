"""Per-layer metrics, one reader per file, found by the metric's name.

Each module has ``read(ctx) -> float | None``, where ``ctx`` is
``bench.run.Context``; a reader that finds nothing to read returns None
and the metric is left out of the result line."""
