"""Output tokens returned in the window over the window's wall seconds."""
from benchmarks.chip.record import window_tokens


def read(rec):
    lo, hi = rec["window"]
    return len(window_tokens(rec)) / (hi - lo)
