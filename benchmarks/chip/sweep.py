#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate that the
engine sustains without a growing backlog.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds <s> \
        --rates <r>,<r>,... [--seed <n>]

Builds the cell's engine once and serves one window per rate, with the
cell's traffic at that rate.  A rate is sustained when the requests still
waiting when the window closed are fewer than one second of arrivals and
the 95th percentile of the time to first token stays under a second.  Prints one JSON line per rate; the
cell's ``rate_per_s`` is then set, once, to 0.8 of the highest sustained
rate.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    from benchmarks.chip import cells

    cell = cells.load(args.workload)
    import jax

    if jax.default_backend() != "tpu" or cell.traffic["loop"] != "open":
        print("no run: needs a TPU and an open-loop cell", file=sys.stderr)
        return 2
    from benchmarks.chip import program
    from benchmarks.chip.driver import Driver
    from benchmarks.chip.metrics import itl_p95_ms, tokens_per_s, ttft_p95_ms
    from benchmarks.chip.run import _compile_cache

    _compile_cache()
    cfg = cell.config
    eng = program.build(cfg, cells.reference(cfg), args.seed)
    program.warm(eng, cell.traffic, cfg["vocab_size"])
    for rate in (float(r) for r in args.rates.split(",")):
        eng.reset_stream()
        traffic = dict(cell.traffic, rate_per_s=rate)
        drv = Driver(eng, traffic, args.seed, cfg["vocab_size"])
        t_open, t_close = drv.run(args.seconds)
        drv.outputs()
        rec = {"window": [t_open, t_close], "steps": drv.steps,
               "requests": drv.requests}
        waiting = sum(1 for r in drv.requests.values()
                      if r["admitted_step"] is None
                      or drv.steps[r["admitted_step"]]["t1"] > t_close)
        ttft = ttft_p95_ms.read(rec)
        print(json.dumps({
            "rate_per_s": rate, "tokens_per_s": tokens_per_s.read(rec),
            "ttft_p95_ms": ttft, "itl_p95_ms": itl_p95_ms.read(rec),
            "waiting_at_close": waiting, "late_s": drv.late_s,
            "sustained": waiting < rate and ttft < 1e3}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
