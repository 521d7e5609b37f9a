# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark orchestrator: runs every paper-table/figure reproduction and
prints one CSV row per measurement (name,us_per_call,derived).

  PYTHONPATH=src python -m benchmarks.run [--only table3,fig9,...] [--quick]
      [--bench-out bench.jsonl] [--require-bench]

``--quick`` is the CI smoke: the kernel/dispatch/autotune/serve benches on
reduced cases, so a regression that only breaks benchmarks fails the
pipeline pre-merge (a couple of minutes, no paper-figure training loops).
``BENCH {json}`` measurement lines are captured per bench: ``--bench-out``
writes them to a jsonl file (CI uploads it as a workflow artifact), and
``--require-bench`` fails any bench that emitted none — a bench that
silently skipped all its cases looks exactly like a green run otherwise.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import sys
import traceback

from benchmarks import (
    autotune,
    bench_compression,
    bench_fig10,
    bench_fig11,
    bench_fig7,
    bench_fig8,
    bench_fig9,
    bench_kernels,
    bench_serve,
    bench_table3,
)
from repro.launch.compile_cache import use_compile_cache

BENCHES = {
    "table3": bench_table3.main,
    "fig7": bench_fig7.main,
    "fig8": bench_fig8.main,
    "fig9": bench_fig9.main,
    "fig10": bench_fig10.main,
    "fig11": bench_fig11.main,
    "kernels": bench_kernels.main,
    "compression": bench_compression.main,
    "serve": bench_serve.main,
    "autotune": autotune.main,
}

# benches with a reduced-case fast mode (main(verbose, quick=True))
QUICK_BENCHES = ("kernels", "autotune", "serve")


class _BenchTee(io.TextIOBase):
    """stdout tee that passes everything through and collects the
    ``BENCH {json}`` measurement lines a bench prints."""

    def __init__(self, real):
        self.real = real
        self.bench_lines: list[str] = []
        self._buf = ""

    def write(self, s: str) -> int:
        n = self.real.write(s)
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line.startswith("BENCH "):
                self.bench_lines.append(line[len("BENCH "):])
        return n

    def flush(self) -> None:
        self.real.flush()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated bench names")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: kernel/dispatch/serve benches, small cases")
    ap.add_argument("--bench-out", default="",
                    help="write every captured BENCH json line to this file")
    ap.add_argument("--require-bench", action="store_true",
                    help="fail any bench that emits no BENCH line (catches "
                         "silently-skipped cases)")
    args = ap.parse_args()
    if args.quick:
        names = [n for n in (args.only.split(",") if args.only else QUICK_BENCHES)
                 if n]
        skipped = [n for n in names if n not in QUICK_BENCHES]
        if skipped:
            print(f"--quick: skipping {skipped} (no fast mode; quick benches "
                  f"are {list(QUICK_BENCHES)})", file=sys.stderr)
        names = [n for n in names if n in QUICK_BENCHES]
        if not names:
            # running nothing must not look green (--require-bench would
            # otherwise be vacuously satisfied)
            print("--quick: no runnable benches selected", file=sys.stderr)
            sys.exit(2)
    else:
        names = [n for n in args.only.split(",") if n] or [
            n for n in BENCHES if n != "autotune"
        ]

    use_compile_cache()
    rows = []
    failed = []
    all_bench_lines = []
    silent = []
    for name in names:
        print(f"=== {name} ===", flush=True)
        tee = _BenchTee(sys.stdout)
        try:
            with contextlib.redirect_stdout(tee):
                fn = BENCHES[name]
                if args.quick and name in QUICK_BENCHES:
                    rows.extend(fn(verbose=True, quick=True))
                else:
                    rows.extend(fn(verbose=True))
        except Exception:  # noqa: BLE001 — report all benches even if one dies
            failed.append(name)
            traceback.print_exc()
        else:
            if not tee.bench_lines:
                silent.append(name)
        all_bench_lines.extend(tee.bench_lines)
    if args.bench_out:
        with open(args.bench_out, "w") as f:
            f.writelines(line + "\n" for line in all_bench_lines)
        print(f"wrote {len(all_bench_lines)} BENCH lines to {args.bench_out}")
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if args.require_bench and silent:
        print(f"NO BENCH LINES from: {silent} (bench ran green but measured "
              f"nothing — cases silently skipped?)", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
    if failed or (args.require_bench and silent):
        sys.exit(1)


if __name__ == "__main__":
    main()
