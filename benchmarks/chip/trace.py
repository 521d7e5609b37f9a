"""Reduces a profiler trace to device busy time, kernel time and idle gaps.

The device's operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named by their HLO instruction text
(``%qsq_matvec_masked.43 = f32[8,49152] custom-call(...)``); the programs
it ran are the events of its ``XLA Modules`` line (``jit_admit(<hash>)``).
A ``while`` op (the scanned layer loop) spans the operations inside it,
so it counts toward busy time but is not listed among the top operations.  The benchmark's own host spans (``bench.submit``,
``bench.step``, ``bench.poll``, ``bench.wait``) come from
``jax.profiler.TraceAnnotation`` and sit on the host plane, on the same
clock.  The traced window runs from the first host span to the end of the
last step that returned inside the benchmark's window.
"""
from __future__ import annotations

import collections
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
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
    """{'ops', 'modules', 'spans'}: lists of (name, start_ns, end_ns)."""
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
                                 if e.name.startswith(SPAN_PREFIX)]
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


def reduce(ev: dict, n_window_steps: int) -> dict:
    """Busy and idle seconds, kernel seconds and the top device operations
    and idle gaps of the traced window."""
    lo, hi = window(ev["spans"], n_window_steps)
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
    # idle gaps, each put down to the innermost host span around its middle
    spans = sorted(_clip(ev["spans"], lo, hi), key=lambda s: s[1])
    idle = collections.Counter()
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    for a, b in zip(edges[::2], edges[1::2], strict=True):
        if b <= a:
            continue
        mid = (a + b) / 2
        inner = [s for s in spans if s[1] <= mid <= s[2]]
        label = min(inner, key=lambda s: s[2] - s[1])[0] if inner else "outside spans"
        idle[label] += b - a
    return {
        "window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
        "gemv_s": kernel_s["gemv"], "gemm_s": kernel_s["gemm"],
        "admit_s": admit_s, "decode_s": decode_s,
        "device_ops": [[n, v / 1e9] for n, v in by_name.most_common(TOP)],
        "idle_gaps": [[n, v / 1e9] for n, v in idle.most_common(TOP)],
    }


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_dir(trace_dir: str, n_window_steps: int) -> dict:
    return reduce(read_xplane(find_xplane(trace_dir)), n_window_steps)
