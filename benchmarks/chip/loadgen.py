"""Seeded request streams, from the parameters of a traffic file.

Sizes come from a fixed base draw (``base_seed`` in the traffic file), so
every run seed serves the same multiset of prompt lengths, output lengths,
tiers and inter-arrival gaps; the run seed only permutes their order and
draws the prompt token ids.  Runs on different seeds then do the same work
and differ in its order, which keeps their spread close to that of two
runs on one seed.

A traffic file gives::

    loop          "closed" (``clients`` waiting callers) or "open"
                  (``rate_per_s`` arrivals, ``arrival``: {"law": "gamma",
                  "cv": c} or {"law": "poisson"})
    prompt_len, output_len
                  {"law": "lognormal", "median": m, "sigma": s,
                   "min": lo, "max": hi}
    tiers         {"hi": weight, ...}
    block         requests per block; each block is one permutation of
                  the base multiset, so every prefix of whole blocks does
                  exactly the same work
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    prompt: tuple[int, ...]
    max_new: int
    tier: str
    offset_s: float  # due time after the window opens (open loop), else 0


def _lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    if spec["law"] != "lognormal":
        raise ValueError(f"unknown length law {spec['law']!r}")
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _gaps(rng: np.random.Generator, traffic: dict, n: int) -> np.ndarray:
    """n inter-arrival gaps with mean exactly 1 / rate."""
    law = traffic["arrival"]["law"]
    if law == "gamma":
        shape = 1.0 / traffic["arrival"]["cv"] ** 2
        g = rng.gamma(shape, 1.0, n)
    elif law == "poisson":
        g = rng.exponential(1.0, n)
    else:
        raise ValueError(f"unknown arrival law {law!r}")
    return g / g.mean() / traffic["rate_per_s"]


def _tiers(traffic: dict, n: int) -> list[str]:
    names = sorted(traffic["tiers"])
    w = np.asarray([traffic["tiers"][t] for t in names], float)
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[np.argsort(-(w / w.sum() * n - counts))[: n - counts.sum()]] += 1
    return [t for t, c in zip(names, counts, strict=True) for _ in range(c)]


def block(traffic: dict) -> list[tuple[int, int, str]]:
    """The base multiset of one block: (prompt_len, max_new, tier)."""
    n = traffic["block"]
    rng = np.random.default_rng(traffic["base_seed"])
    p = _lengths(rng, traffic["prompt_len"], n)
    o = _lengths(rng, traffic["output_len"], n)
    t = _tiers(traffic, n)
    return [(int(a), int(b), c) for a, b, c in zip(p, o, t, strict=True)]


def stream(traffic: dict, seed: int, n: int, vocab: int) -> list[Request]:
    """The first ``n`` requests of the stream for ``seed``."""
    rng = np.random.default_rng(seed)
    base = block(traffic)
    out: list[Request] = []
    while len(out) < n:
        perm = rng.permutation(len(base))
        for i in perm:
            plen, max_new, tier = base[i]
            out.append(Request(tuple(int(x) for x in
                                     rng.integers(0, vocab, plen)),
                               max_new, tier, 0.0))
    return out[:n]


def open_schedule(traffic: dict, seed: int, seconds: float,
                  vocab: int) -> list[Request]:
    """Requests due in a window of ``seconds``: rate * seconds of them,
    the gaps a permutation of a fixed base draw, the first due at 0."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    gaps = _gaps(np.random.default_rng(traffic["base_seed"] + 1), traffic, n)
    rng = np.random.default_rng(seed)
    gaps = gaps[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due *= seconds / (due[-1] + gaps[-1])  # the n gaps fill the window
    reqs = stream(traffic, seed ^ 0x5EED, n, vocab)
    return [dataclasses.replace(r, offset_s=float(t))
            for r, t in zip(reqs, due, strict=True)]
