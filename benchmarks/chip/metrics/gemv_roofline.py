"""Least time the chip needs for the traced window's packed GEMVs over
the device time of the GEMV kernel events.  The GEMVs are every packed
weight of each decode dispatch (its live lanes' rows, the planes its
demand floor keeps) and the output head of each admission, whose prefill
keeps only the last position (one row, at the request's tier).  Memory
bandwidth bounds these calls."""
from benchmarks.chip import work
from benchmarks.chip.record import in_window, window_steps


def read(rec):
    if rec["peaks"] is None:  # no chip, no peak
        return None
    tr = rec["trace"]
    if not tr or not tr["gemv_s"]:
        return None
    cfg, pk = rec["config"], rec["peaks"]
    shapes, vectors = cfg["packed_shapes"], cfg["tier_vectors"]
    head = {cfg["head"]: vectors[cfg["head"]]}
    calls = []
    for s in window_steps(rec):
        if s["demand"] is not None and s["live"]:
            calls += work.dispatch_calls(shapes, vectors, s["demand"],
                                         s["live"], cfg["group"])
    for r in rec["requests"].values():
        a = r["admitted_step"]
        if a is not None and in_window(rec, rec["steps"][a]["t1"]):
            calls += work.dispatch_calls(shapes, head,
                                         cfg["tier_order"].index(r["tier"]),
                                         1, cfg["group"])
    t, _, _ = work.least_time(calls, pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    return 100.0 * t / tr["gemv_s"]
