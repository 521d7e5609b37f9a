"""Host time of the window's steps that admitted a request, per admission
(such a step also decodes; it ends in its own device sync)."""
from benchmarks.chip.record import window_steps


def read(rec):
    steps = [s for s in window_steps(rec) if s["admitted"]]
    n = sum(len(s["admitted"]) for s in steps)
    return 1e3 * sum(s["t1"] - s["t0"] for s in steps) / n if n else None
