"""One reader per per-layer metric, found by the metric's name in
``BENCHMARK.json``: ``read(run) -> float | None``.  ``run`` holds the
reduced trace (``bench.trace.Trace``), the cell's counts for the traced
window, the configuration, the workload, the chip count, the chip's
peaks and the window's length.  A reader that finds nothing to read
returns None, and the metric is left out of the result."""
