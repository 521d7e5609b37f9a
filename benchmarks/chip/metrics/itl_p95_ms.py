"""95th percentile of every gap between consecutive output tokens of a
request whose later token returned in the window."""
from benchmarks.chip.record import in_window, p95, token_times


def read(rec):
    gaps = []
    for r in rec["requests"].values():
        ts = token_times(rec, r)
        gaps += [b - a for a, b in zip(ts, ts[1:]) if in_window(rec, b)]
    return 1e3 * p95(gaps) if gaps else None
