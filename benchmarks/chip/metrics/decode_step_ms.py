"""Median host time of the window's steps that admitted nothing."""
import numpy as np

from benchmarks.chip.record import window_steps


def read(rec):
    ts = [s["t1"] - s["t0"] for s in window_steps(rec)
          if not s["admitted"] and s["live"]]
    return 1e3 * float(np.median(ts)) if ts else None
