"""Share of the traced window's gate fetches (``repro:serve.gate`` in
``serve/continuous.py``) that the per-client gate LRU already held, in
%.

A traced run records the first ``trace_seconds`` after a warm-up of one
request per prompt bucket, so the LRU starts the window nearly empty:
some sixty admissions cannot fill 256 entries, and the reading is mostly
compulsory misses, not the steady state of a long run.  A change to
gate fetch moves it only as far as it helps a cold cache."""
from bench import program_spans


def read(run):
    prof = program_spans.of(run)
    if prof is None:
        return None
    hits, n = program_spans.gate_hits(prof)
    return 100.0 * hits / n if n else None
