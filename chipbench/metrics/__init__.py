"""Per-layer metrics, one module each, found by the metric's name.

Each module defines ``read(ctx) -> float | None`` over a
``run.TraceContext``: the traced window's device operations and host
spans, the cell and the peaks.  A reader that finds nothing returns None
and the metric is left out of the run's line."""
