"""95th percentile, over every request due in the window, of the time
from when it was due (open loop) or submitted (closed loop) to the return
of the step that admitted it.  A request that never got a first token
counts with the time until the run's last step, so it can only raise the
tail."""
from benchmarks.chip.record import due_in_window, first_token_time, p95


def read(rec):
    end = rec["steps"][-1]["t1"]
    waits = []
    for r in due_in_window(rec):
        t = first_token_time(rec, r)
        waits.append((end if t is None else t) - r["due"])
    return 1e3 * p95(waits) if waits else None
