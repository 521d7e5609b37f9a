"""Share of the chip's bf16 peak inside the decode programs: the operations
the traced window's decoded tokens need (2 x every matmul weight, plus
attention over the token's context) over the device time of the decode
programs in the trace."""
from benchmarks.chip import work
from benchmarks.chip.record import window_tokens


def read(rec):
    if rec["peaks"] is None:  # no chip, no peak
        return None
    tr = rec["trace"]
    if not tr or not tr["decode_s"]:
        return None
    cfg = rec["config"]
    per_token = work.weight_flops_per_token(cfg["matmul_shapes"])
    att = cfg["attention_flops_per_context"]
    flops = sum(per_token + att * (r["prompt_len"] + j)
                for r, j, _ in window_tokens(rec) if j >= 1)
    return 100.0 * flops / tr["decode_s"] / rec["peaks"]["bf16_flops_per_s"]
