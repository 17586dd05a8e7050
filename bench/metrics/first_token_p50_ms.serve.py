"""Median, over the admissions that open in the traced window, of the
time from a request's due time to the end of its prefill on the device,
which makes its first token: the wait the engine recorded at admission
(``repro:serve.admit``'s ``wait_us``) plus the admission span's start to
the end of the prefill execution it launched (paired by the profiler's
launch links, never by order), in ms.  Admissions whose prefill ends
after the window, or whose launch the trace does not link, are left out
(the run's log counts them).  A median, because a window holds some
sixty admissions.  The window is the first ``trace_seconds`` of the run,
so the gate cache is cold in it (see ``gate_hit_ratio.serve``)."""
import statistics

from bench import program_spans


def read(run):
    prof = program_spans.of(run)
    if prof is None:
        return None
    ms, _, _ = program_spans.first_token_ms(prof)
    return statistics.median(ms) if ms else None
