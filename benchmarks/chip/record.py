"""The record of one run, and the arithmetic the metric readers share.

A record is a plain dict, so a test can build one by hand:

    window     [t_open, t_close]: host seconds (``time.perf_counter``)
    steps      one entry per ``engine.step()``: ``t0``, ``t1`` (its start
               and its return, which ends in a device sync) and every field
               of the engine's ``StepInfo`` (tuples as lists), among them
               ``admitted`` (request ids), ``live`` (decoding lanes),
               ``demand`` (plane-demand floor of its decode dispatch, None
               if none)
    requests   rid -> ``due`` (host seconds: scheduled arrival in an open
               loop, submission in a closed one), ``prompt_len``,
               ``max_new``, ``tier``, ``admitted_step``,
               ``finished_step`` (indices into ``steps``, None if never),
               ``n_tokens``, ``done``
    config     what the work-counting readers need (``work_config``), with
               the configuration itself under ``model``
    trace      the reduced device trace (``trace.reduce``) or None
    batch_slots, setup_s, peaks, memory_peak_bytes

Tokens are placed by step: a request admitted at step ``a`` gets its
first token from the admission prefill and its second from the decode
dispatch of the same step, then one per step, so token ``j >= 1``
returns with step ``a + j - 1``.  These cells do not speculate, so a
request that emits ``n >= 2`` tokens finishes at step ``a + n - 2``.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip import work


def in_window(rec: dict, t: float) -> bool:
    lo, hi = rec["window"]
    return lo <= t <= hi


def token_steps(req: dict) -> list[int]:
    """Step index of each token the request emitted."""
    a, n = req["admitted_step"], req["n_tokens"]
    if a is None or n == 0:
        return []
    return [a] + [a + j - 1 for j in range(1, n)]


def token_times(rec: dict, req: dict) -> list[float]:
    return [rec["steps"][s]["t1"] for s in token_steps(req)]


def check_token_steps(rec: dict) -> list[str]:
    """Requests whose finish step disagrees with one token per step."""
    bad = []
    for rid, r in rec["requests"].items():
        if r["done"] and r["n_tokens"] >= 1:
            want = r["admitted_step"] + max(r["n_tokens"] - 2, 0)
            if r["finished_step"] != want:
                bad.append(f"request {rid}: {r['n_tokens']} tokens, admitted "
                           f"at step {r['admitted_step']}, finished at "
                           f"{r['finished_step']} (expected {want})")
    return bad


def due_in_window(rec: dict) -> list[dict]:
    lo, hi = rec["window"]
    return [r for r in rec["requests"].values() if lo <= r["due"] <= hi]


def window_steps(rec: dict) -> list[dict]:
    return [s for s in rec["steps"] if in_window(rec, s["t1"])]


def window_tokens(rec: dict) -> list[tuple[dict, int, float]]:
    """(request, token index, time) of every token returned in the window."""
    out = []
    for r in rec["requests"].values():
        for j, t in enumerate(token_times(rec, r)):
            if in_window(rec, t):
                out.append((r, j, t))
    return out


def first_token_time(rec: dict, req: dict) -> float | None:
    if req["admitted_step"] is None or req["n_tokens"] == 0:
        return None
    return rec["steps"][req["admitted_step"]]["t1"]


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, float), 95))


def work_config(cfg: dict, ref) -> dict:
    """What the work-counting readers need of a configuration.  The leaves
    the reference lists in ``ROUTED`` (rows routed to experts, not read by
    every live row) stay out of ``packed_shapes`` and ``tier_vectors``,
    which count dense leaves, and are given under ``routed_shapes``;
    ``matmul_shapes`` is what one token passes through."""
    shapes = ref.matmul_shapes(cfg)
    q = cfg["quant"]
    routed = set(getattr(ref, "ROUTED", ()))
    dense = [p for p in q["packed"] if p not in routed]
    return {"matmul_shapes": shapes,
            "packed_shapes": {p: shapes[p] for p in dense},
            "routed_shapes": {p: shapes[p] for p in sorted(routed)},
            "head": ref.HEAD, "group": q["group"], "tier_order": q["tiers"],
            "tier_vectors": work.tier_vectors(q["drops"], q["tiers"], dense),
            "attention_flops_per_context": ref.attention_flops_per_token(cfg, 1.0),
            "model": cfg}
