"""The comparison that decides ``correct`` for a served model.

After the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed with the longest
among them, is run through the plain float32 reference at each request's
own tier, over its prompt and the tokens it was served.  At every served
position the number read is the gap by which the served token's reference
logit lies below the reference's best logit: greedy serving that computes
what the reference computes reads about 0, rounding flips near-ties by
little, and a wrong token reads a gap of the logits' own scale.  The
number compared is the widest gap.  The control reads, at the same
positions, the gap of the token that the reference computed with every
matmul in float8 puts first.
"""
from __future__ import annotations

import numpy as np


def sample(reqs: list[dict], seed: int, min_tokens: int) -> list[dict]:
    """The longest request, then the others in seeded order taking the
    tiers in turn, until ``min_tokens`` served tokens are in the sample."""
    if not reqs:
        return []
    rng = np.random.default_rng(seed)
    order = sorted(range(len(reqs)), key=lambda i: -len(reqs[i]["tokens"]))
    picked = [order[0]]
    rest = [order[i] for i in rng.permutation(np.arange(1, len(order)))]
    by_tier: dict[str, list[int]] = {}
    for i in rest:
        by_tier.setdefault(reqs[i]["tier"], []).append(i)
    total = len(reqs[picked[0]]["tokens"])
    tiers = sorted(by_tier)
    while any(by_tier.values()):
        for t in tiers:
            if by_tier[t] and (total < min_tokens
                               or t not in {reqs[i]["tier"] for i in picked}):
                i = by_tier[t].pop(0)
                picked.append(i)
                total += len(reqs[i]["tokens"])
        if total >= min_tokens and all(
                t in {reqs[i]["tier"] for i in picked} for t in tiers):
            break
    return [reqs[i] for i in picked]


def _pad(n: int, step: int) -> int:
    return -(-n // step) * step


def gaps(ref, seed: int, model: dict, tier_drops: dict[str, dict[str, int]],
         reqs: list[dict], control: bool = False) -> dict:
    """Widest served-token gap over ``reqs`` (and the control's, with
    ``control``), computed tier by tier with ``ref`` (a reference module)."""
    out = {"served": 0.0, "control": 0.0 if control else None, "tokens": 0,
           "by_tier": {}}
    for tier in sorted({r["tier"] for r in reqs}):
        rows = [r for r in reqs if r["tier"] == tier]
        seqs = [list(r["prompt"]) + list(r["tokens"][:-1]) for r in rows]
        b = 1 << (len(rows) - 1).bit_length()
        t = _pad(max(len(s) for s in seqs), 64)
        toks = np.zeros((b, t), np.int32)
        tgt = np.zeros((b, t), np.int32)
        mask = np.zeros((b, t), bool)
        for i, (r, s) in enumerate(zip(rows, seqs, strict=True)):
            toks[i, :len(s)] = s
            p, n = len(r["prompt"]), len(r["tokens"])
            tgt[i, p - 1:p - 1 + n] = r["tokens"]
            mask[i, p - 1:p - 1 + n] = True
        w = ref.draw(seed, model, tier_drops[tier])
        h = ref.final_hidden(w, model, toks, low=False)
        g = np.asarray(ref.served_gaps(h, w["embed/head"], tgt))[mask]
        out["by_tier"][tier] = float(g.max())
        out["served"] = max(out["served"], float(g.max()))
        out["tokens"] += int(mask.sum())
        if control:
            h_low = ref.final_hidden(w, model, toks, low=True)
            c = np.asarray(ref.control_gaps(h, h_low, w["embed/head"]))[mask]
            out["control"] = max(out["control"], float(c.max()))
        del w, h
    return out
