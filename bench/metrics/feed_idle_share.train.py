"""Share of the traced training window in which the chips sat idle while
the host staged a chunk of rounds for the epoch program (the program's
``repro:train.feed`` spans in ``core/adasplit.py``: drawing the batches,
stacking them, putting them on the device), averaged over chips, in %."""
from bench import program_spans


def read(run):
    prof = program_spans.of(run)
    win = run["window_s"]
    if prof is None or win <= 0:
        return None
    idle = program_spans.idle_inside(prof, "train.feed")
    return None if idle is None else 100.0 * idle / win
