"""Reduces a profiler trace to device busy time, kernel time and idle gaps.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named by their HLO instruction text
(``%qsq_matvec_masked.43 = f32[8,49152] custom-call(...)``); the programs
it ran are the events of its ``XLA Modules`` line (``jit_admit(<hash>)``).
A ``while`` op (the scanned layer loop) spans the operations inside it,
so it counts toward busy time but is not listed among the operations.
The benchmark's own host spans (``bench.submit``, ``bench.step``,
``bench.poll``, ``bench.wait``) and the program's (``serve.step`` and the
spans inside it) come from ``jax.profiler.TraceAnnotation`` and sit on the
host plane, on the same clock.  The traced window runs from the first
``bench.*`` span to the end of the last step that returned inside the
benchmark's window.
"""
from __future__ import annotations

import collections
import glob
import heapq
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."  # the benchmark's spans: the window, ``idle_gaps``
PROGRAM_PREFIX = "serve."  # the program's spans: ``spans``, ``idle_by_span``
# kernel event names, matched as substrings
KERNELS = {"gemv": ("qsq_matvec",), "gemm": ("qsq_matmul",)}
ADMIT_PROGRAM = "jit_admit"  # the admission program: prefill + lane insert
DECODE_PROGRAM = "jit_cont_step"  # the decode dispatch over every slot
CONTAINERS = ("while", "conditional", "call")  # ops that hold other ops
TOP = 10


def op_name(event_name: str) -> str:
    """``%qsq_matvec_masked.43 = f32[...] ...`` -> ``qsq_matvec_masked``."""
    head = event_name.split(" = ")[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def read_xplane(path: str) -> dict:
    """{'ops', 'modules', 'spans'}: lists of (name, start_ns, end_ns);
    ``spans`` holds the benchmark's and the program's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"ops": [], "modules": [], "spans": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    out[key] += [(e.name, e.start_ns, e.end_ns)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"] += [(e.name, e.start_ns, e.end_ns)
                                 for e in line.events
                                 if e.name.startswith((SPAN_PREFIX,
                                                       PROGRAM_PREFIX))]
    return out


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(events, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events if b > lo and a < hi]


def window(spans, n_window_steps: int) -> tuple[float, float]:
    """(lo, hi) in trace nanoseconds: the first span's start to the end of
    step ``n_window_steps`` (1-based)."""
    steps = sorted((a, b) for n, a, b in spans if n == SPAN_PREFIX + "step")
    lo = min(a for _, a, _ in spans)
    return lo, steps[min(n_window_steps, len(steps)) - 1][1]


def idle_by_span(gaps, spans) -> collections.Counter:
    """Nanoseconds of each gap (in time order) put down to the innermost
    (shortest) span around its middle, or to ``outside spans``."""
    spans = sorted(spans, key=lambda s: s[1])
    idle, open_, i = collections.Counter(), [], 0
    for a, b in gaps:
        mid = (a + b) / 2
        while i < len(spans) and spans[i][1] <= mid:
            name, start, end = spans[i]
            heapq.heappush(open_, (end - start, i, end, name))
            i += 1
        while open_ and open_[0][2] < mid:  # ended: every later middle is later
            heapq.heappop(open_)
        idle[open_[0][3] if open_ else "outside spans"] += b - a
    return idle


def reduce(ev: dict, n_window_steps: int) -> dict:
    """Busy and idle seconds, kernel seconds, the seconds of every device
    operation (``op_s``; ``device_ops`` its top ten), the program's spans
    in the window (``spans``: name, start and end in seconds from the
    window's start) and the idle time by innermost span: ``idle_gaps``
    over the benchmark's spans, ``idle_by_span`` over both."""
    bench = [s for s in ev["spans"] if s[0].startswith(SPAN_PREFIX)]
    lo, hi = window(bench, n_window_steps)
    ops = _clip(ev["ops"], lo, hi)
    busy = merge((a, b) for _, a, b in ops)
    busy_ns = sum(b - a for a, b in busy)
    by_name = collections.Counter()
    for n, a, b in ops:
        name = op_name(n)
        if name not in CONTAINERS:
            by_name[name] += b - a
    kernel_s = {k: sum(b - a for n, a, b in ops if any(s in n for s in subs)) / 1e9
                for k, subs in KERNELS.items()}
    modules = _clip(ev["modules"], lo, hi)
    admit_s = sum(b - a for n, a, b in modules if n.startswith(ADMIT_PROGRAM)) / 1e9
    decode_s = sum(b - a for n, a, b in modules if n.startswith(DECODE_PROGRAM)) / 1e9
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2], strict=True) if b > a]
    clipped = _clip(ev["spans"], lo, hi)
    idle = idle_by_span(gaps, [s for s in clipped if s[0].startswith(SPAN_PREFIX)])
    program = sorted(((n, a, b) for n, a, b in ev["spans"]
                      if n.startswith(PROGRAM_PREFIX) and lo <= a and b <= hi),
                     key=lambda s: s[1])
    return {
        "window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
        "gemv_s": kernel_s["gemv"], "gemm_s": kernel_s["gemm"],
        "admit_s": admit_s, "decode_s": decode_s,
        "device_ops": [[n, v / 1e9] for n, v in by_name.most_common(TOP)],
        "idle_gaps": [[n, v / 1e9] for n, v in idle.most_common(TOP)],
        "op_s": {n: v / 1e9 for n, v in by_name.most_common()},
        "spans": [(n, (a - lo) / 1e9, (b - lo) / 1e9) for n, a, b in program],
        "idle_by_span": {n: v / 1e9 for n, v in
                         idle_by_span(gaps, clipped).most_common()},
    }


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_dir(trace_dir: str, n_window_steps: int) -> dict:
    return reduce(read_xplane(find_xplane(trace_dir)), n_window_steps)
