#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by name, builds the packed
per-request engine from weights drawn from the seed, warms up the cell's
shapes, serves one window and checks what it served against the plain
reference.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` a shorter window runs under the profiler and
the metrics are the per-layer ones.  The last line of standard output is
one JSON object; the numbers compared for ``correct`` close standard
error and the result's ``checks`` key.  Without a TPU, or with fewer
chips than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
TRACE_WINDOW_S = 2.0  # a traced run serves at most this long: a trace of
# SmolLM-135M's 30 scanned layers holds ~460k device events a second


def _paths() -> None:
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def _compile_cache() -> None:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.1f} s] {msg}", file=sys.stderr,
          flush=True)


def _profile_options():
    """Device and TraceAnnotation events only: the Python tracer would
    record every call of the host loop and slow it many times over."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


class _CompileCount:
    """Counts programs traced, to show that nothing new is traced, compiled
    or loaded from the compile cache inside the window."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.n += 1


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float = T_START) -> dict:
    """One run of ``cell`` on whatever backend JAX has; returns the result
    object (the caller decides whether that backend is acceptable)."""
    import jax

    from benchmarks.chip import cells, check, peaks, program
    from benchmarks.chip import trace as xtrace
    from benchmarks.chip.driver import Driver
    from benchmarks.chip.record import (check_token_steps, due_in_window,
                                        work_config)

    cfg, traffic = cell.config, cell.traffic
    ref = cells.reference(cfg, cell.here)
    dev = jax.devices()[0]
    pk = peaks.peak(dev.device_kind) if dev.platform == "tpu" else None
    compiles = _CompileCount()
    eng = program.build(cfg, ref, seed, log=_log, here=cell.here)
    plan_faults = program.tier_plan_faults(eng, cfg["quant"])
    t = time.perf_counter()
    program.warm(eng, traffic, cfg["vocab_size"])
    _log(f"warmed up: {time.perf_counter() - t:.1f} s")
    window = min(seconds, TRACE_WINDOW_S) if trace else seconds
    drv = Driver(eng, traffic, seed, cfg["vocab_size"], spans=trace)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    opened = {}

    def on_open():
        if trace:
            jax.profiler.start_trace(tdir, profiler_options=_profile_options())
        opened["setup_s"] = time.perf_counter() - t_start
        opened["compiles"] = compiles.n

    t_open, t_close = drv.run(window, on_open)
    _log(f"window served: {len(drv.requests)} requests, {len(drv.steps)} steps")
    setup_s = opened["setup_s"]
    n_window_steps = sum(1 for s in drv.steps
                         if s["t0"] >= t_open and s["t1"] <= t_close)
    compiled_in_window = compiles.n - opened["compiles"]
    if trace:
        jax.profiler.stop_trace()
    outputs = drv.outputs()
    _log("drained")
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    del eng, drv.eng
    gc.collect()

    rec = {
        "window": [t_open, t_close], "steps": drv.steps,
        "requests": drv.requests, "batch_slots": cfg["serve"]["batch_slots"],
        "setup_s": setup_s, "memory_peak_bytes": memory_peak, "peaks": pk,
        "config": work_config(cfg, ref), "trace": None,
    }

    # correctness: the reference over a seeded sample of finished requests
    done = [o for o in outputs if o["done"]]
    chosen = check.sample(done, seed, cfg["check"]["min_tokens"])
    q = cfg["quant"]
    drops = {t: {p: 1 for p in q["drops"][t]} for t in q["tiers"]}
    g = check.gaps(ref, seed, cfg, drops, chosen)
    _log(f"checked {g['tokens']} served tokens against the reference")
    due = due_in_window(rec)
    failed = sum(1 for r in due if not r["done"])
    step_faults = check_token_steps(rec)
    checks = {
        "max_gap": {"value": g["served"], "limit": cfg["check"]["max_gap"]},
        "tokens_compared": {"value": g["tokens"],
                            "limit": cfg["check"]["min_tokens"]},
        "step_faults": {"value": len(step_faults), "limit": 0},
        "tier_plan_faults": {"value": len(plan_faults), "limit": 0},
    }
    correct = (g["served"] <= cfg["check"]["max_gap"]
               and g["tokens"] >= min(cfg["check"]["min_tokens"],
                                      sum(len(o["tokens"]) for o in done))
               and not step_faults and not plan_faults and bool(chosen))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": len(due), "failed": failed}
    if trace:
        rec["trace"] = xtrace.reduce_dir(tdir, n_window_steps)
        _log("trace reduced")
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    metrics = {}
    for m in wanted:
        v = cells.reader(m["name"], cell.here)(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    result["checks"] = checks
    # what a reader of the run needs beside the result, with the
    # end-to-end readings that no bound holds in this cell
    result["_notes"] = {"compiled_in_window": compiled_in_window,
                        "open_loop_late_s": drv.late_s,
                        "sampled": len(chosen), "by_tier": g["by_tier"],
                        "step_faults": step_faults[:3],
                        "tier_plan_faults": plan_faults[:3],
                        "readings": {n: cells.reader(n, cell.here)(rec)
                                     for n in ("ttft_p95_ms", "tokens_per_s")}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    from benchmarks.chip import cells

    cell = cells.load(args.workload)
    import jax

    backend = jax.default_backend()
    if backend != "tpu" or len(jax.devices()) < cell.chips:
        print(f"no run: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"has {len(jax.devices())} {backend} device(s)", file=sys.stderr)
        return 2
    _compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    notes = result.pop("_notes")
    print(f"notes: {json.dumps(notes)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
