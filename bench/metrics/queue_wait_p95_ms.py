"""95th percentile of the time a request waited from its due time to its
admission into a decode slot (``serve/continuous.py`` and
``serve/scheduler.py``), over the requests due in the traced window, in
ms; a request never admitted waits forever."""
import math

from bench.harness import percentile


def read(run):
    waits = run["counts"].get("queue_wait_ms")
    if not waits:
        return None
    p95 = percentile(waits, 95)
    return p95 if math.isfinite(p95) else None
