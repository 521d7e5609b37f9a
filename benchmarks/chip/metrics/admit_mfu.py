"""Share of the chip's bf16 peak inside the admission programs: the
operations the traced window's admissions need (2 x every matmul weight
per prompt token, the output head once for the last position, and causal
attention over the prompt) over the device time of the admission programs
in the trace."""
from benchmarks.chip.record import in_window


def read(rec):
    if rec["peaks"] is None:  # no chip, no peak
        return None
    tr = rec["trace"]
    if not tr or not tr["admit_s"]:
        return None
    cfg = rec["config"]
    per_row = {p: 2.0 * n * k * m for p, (n, k, m) in cfg["matmul_shapes"].items()}
    head = per_row.pop(cfg["head"])
    body = sum(per_row.values())
    flops = 0.0
    for r in rec["requests"].values():
        a = r["admitted_step"]
        if a is not None and in_window(rec, rec["steps"][a]["t1"]):
            p = r["prompt_len"]
            flops += (body * p + head
                      + cfg["attention_flops_per_context"] * p * (p + 1) / 2)
    return 100.0 * flops / tr["admit_s"] / rec["peaks"]["bf16_flops_per_s"]
