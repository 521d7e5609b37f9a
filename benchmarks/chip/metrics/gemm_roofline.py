"""Least time the chip needs for the traced admission prefills' packed
GEMMs (every packed weight but the output head, the prompt's rows, at
the request's own tier) over the device time of the GEMM kernel events."""
from benchmarks.chip import work
from benchmarks.chip.record import in_window


def read(rec):
    if rec["peaks"] is None:  # no chip, no peak
        return None
    tr = rec["trace"]
    if not tr or not tr["gemm_s"]:
        return None
    cfg, pk = rec["config"], rec["peaks"]
    vectors = {p: v for p, v in cfg["tier_vectors"].items() if p != cfg["head"]}
    calls = []
    for r in rec["requests"].values():
        a = r["admitted_step"]
        if a is not None and in_window(rec, rec["steps"][a]["t1"]):
            calls += work.dispatch_calls(cfg["packed_shapes"], vectors,
                                         cfg["tier_order"].index(r["tier"]),
                                         r["prompt_len"], cfg["group"])
    t, _, _ = work.least_time(calls, pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    return 100.0 * t / tr["gemm_s"]
