#!/usr/bin/env python3
"""Readings that set the limit of ``correct``: the program's widest
served-token gap and the control's, seed by seed, in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n>,<n>,...

For each seed it builds the cell's engine, serves one short window at the
cell's own load, and reads over the same seeded sample of finished
requests both the program's gap and the gap of the control: the plain
reference with every matmul in float8 (the precision below the
configuration's bfloat16), put first at each served position.  The
benchmark's own runs never run the control.  Prints one JSON line per
seed and needs a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def readings(cell, seed: int, seconds: float) -> dict:
    from benchmarks.chip import cells, check, program
    from benchmarks.chip.driver import Driver

    cfg, q = cell.config, cell.config["quant"]
    ref = cells.reference(cfg, cell.here)
    t0 = time.perf_counter()
    eng = program.build(cfg, ref, seed, log=lambda m: print(m, file=sys.stderr),
                        here=cell.here)
    plan_faults = program.tier_plan_faults(eng, q)
    program.warm(eng, cell.traffic, cfg["vocab_size"])
    drv = Driver(eng, cell.traffic, seed, cfg["vocab_size"])
    drv.run(seconds)
    outputs = drv.outputs()
    del eng, drv.eng
    gc.collect()
    done = [o for o in outputs if o["done"]]
    chosen = check.sample(done, seed, cfg["check"]["min_tokens"])
    drops = {t: {p: 1 for p in q["drops"][t]} for t in q["tiers"]}
    g = check.gaps(ref, seed, cfg, drops, chosen, control=True)
    return {"seed": seed, "program_gap": g["served"], "control_gap": g["control"],
            "tokens": g["tokens"], "by_tier": g["by_tier"],
            "tier_plan_faults": plan_faults,
            "finished": len(done), "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    from benchmarks.chip import cells

    cell = cells.load(args.workload)
    import jax

    if jax.default_backend() != "tpu" or len(jax.devices()) < cell.chips:
        print(f"no run: cell {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    from benchmarks.chip.run import _compile_cache

    _compile_cache()
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s), args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
