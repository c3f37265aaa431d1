"""Per-layer metric readers, one module per metric, each with
``read(run) -> float | None`` (``run`` is ``bench.run.Context``). A reader
that finds nothing to read returns None and the metric is left out."""
