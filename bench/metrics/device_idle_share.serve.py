"""Share of the traced serving window in which no operation ran on the
chips (averaged over chips), in %."""
from bench import trace


def read(run):
    tr, win = run["trace"], run["window_s"]
    if not tr.ops or win <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(tr) / win)
