"""Mean share of the batch slots decoding, over the window's steps."""
import numpy as np

from benchmarks.chip.record import window_steps


def read(rec):
    steps = window_steps(rec)
    if not steps:
        return None
    return 100.0 * float(np.mean([s["live"] for s in steps])) / rec["batch_slots"]
