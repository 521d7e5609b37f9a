"""Operations and bytes the algorithm needs, from shapes and tier drops.

A packed matmul of ``rows`` rows against a (K, N) weight stored as 3-bit
bit-planes needs, at a dispatch whose demand floor keeps ``planes`` of
the three planes: ``2 * rows * K * N`` operations, and the bytes of the
kept planes (``planes * K / 32 * N`` int32 words), the f32 group scales
(``K / group * N``), the bf16 input rows and the bf16 output rows.  What
an implementation adds on top (a kernel that decodes a weight tile once
per mask variant, padded rows, a copied cache) is not counted, so it
lowers a roofline share instead of raising the count.
"""
from __future__ import annotations

N_PLANES = 3
WORD_BYTES = 4   # one int32 word of a bit-plane holds 32 weights
SCALE_BYTES = 4  # f32 group scales
ACT_BYTES = 2    # bf16 activations in and out


def packed_call(k: int, n: int, rows: int, group: int,
                planes: int) -> tuple[float, float]:
    """(operations, bytes) of one packed matmul call."""
    flops = 2.0 * rows * k * n
    nbytes = (planes * (k // 32) * n * WORD_BYTES
              + (k // group) * n * SCALE_BYTES
              + rows * k * ACT_BYTES + rows * n * ACT_BYTES)
    return flops, float(nbytes)


def tier_vectors(tiers: dict[str, list[str]], order: list[str],
                 packed: list[str]) -> dict[str, tuple[int, ...]]:
    """Path -> planes each tier (in ``order``) drops from that leaf, given
    ``tiers`` {tier: [paths that drop one plane]}."""
    return {p: tuple(int(p in tiers[t]) for t in order) for p in packed}


def demand_drop(vector: tuple[int, ...], demand: int) -> int:
    """Planes a dispatch at demand floor ``demand`` may skip on a leaf:
    the fewest any tier at or below the floor drops (a suffix minimum)."""
    return min(vector[demand:])


def dispatch_calls(shapes: dict[str, tuple[int, int, int]],
                   vectors: dict[str, tuple[int, ...]], demand: int,
                   rows: int, group: int) -> list[tuple[float, float]]:
    """(operations, bytes) of every packed matmul call of one forward over
    ``rows`` rows at demand floor ``demand``: one call per leaf per layer."""
    calls = []
    for path, vec in vectors.items():
        layers, k, n = shapes[path]
        planes = N_PLANES - demand_drop(vec, demand)
        calls += [packed_call(k, n, rows, group, planes)] * layers
    return calls


def least_time(calls: list[tuple[float, float]], flops_per_s: float,
               bytes_per_s: float) -> tuple[float, float, float]:
    """(seconds, compute-bound seconds, memory-bound seconds): the least
    time the chip needs for ``calls``, each bound by the larger of its
    operations over peak and its bytes over bandwidth."""
    total = comp = mem = 0.0
    for f, b in calls:
        tc, tm = f / flops_per_s, b / bytes_per_s
        total += max(tc, tm)
        comp += tc if tc >= tm else 0.0
        mem += tm if tm > tc else 0.0
    return total, comp, mem


def weight_flops_per_token(shapes: dict[str, tuple[int, int, int]]) -> float:
    """2 x every matmul weight a token passes through."""
    return 2.0 * sum(layers * k * n for layers, k, n in shapes.values())
