"""Shape-aware kernel dispatch for the packed QSQ matmul.

Every ``PackedWeight.matmul`` lands here.  The dispatcher keys on
(M, K, N, G, backend) and routes to the best available path:

* ``pallas_gemv`` — the small-M decode kernel (`qsq_matvec.py`): one M
  block, VMEM scratch accumulator, GEMV-proportioned tiles;
* ``pallas_gemm`` — the tiled MXU kernel (`qsq_matmul.py`) for prefill /
  train shapes;
* ``xla_ref``     — the pure-XLA reference (`ref.qsq_matmul_ref`), used
  when the kernel switch (`quant.store.set_packed_matmul_kernel(False)`)
  is off.  It still consumes the packed representation — there is no
  dense-weight fallback path anywhere in dispatch.

Shapes that don't divide the chosen tile are **zero-padded** to it (M up
to the sublane, N up to the lane/tile, K never — a K with no lane-aligned
divisor is tiled whole, which the TPU lowering accepts as a full-array
block dimension).  Zero x rows and zero plane words contribute
exact zeros, so padding changes no output value; the pad is sliced off
after the kernel.  This eliminates the old behaviour where a tile-ragged
shape silently materialized the whole dense weight inside jit.

Tile configs resolve, in order, from:
1. an exact (backend, M, K, N, G) entry in the tuned table,
2. the backend's shape-class default ("gemv" / "gemm") in the table,
3. built-in heuristics.

The tuned table is a checked-in JSON (`kernels/tuned_tiles.json`) written
by ``benchmarks/autotune.py``; point ``REPRO_TUNED_TABLE`` at another file
(or call :func:`set_tuned_table`) for a data-driven override.

Dispatch decisions are counted in :data:`counters` (trace-time, keyed by
route and ``route:padded|exact``) so tests and benchmarks can assert which
path a shape took.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import math
import os
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core import codec
from repro.kernels import ref

PLANE = codec.PLANE_GROUP

# M at or below this routes to the GEMV kernel (decode shapes: batch slots
# x one token).  Above it the MXU GEMM tiling wins.
GEMV_M_MAX = 16

# TPU register tiling: f32 sublane x lane.  Padded tiles honor these so a
# plan that validates in interpret mode is also Mosaic-legal.
SUBLANE = 8
LANE = 128

ROUTE_GEMV = "pallas_gemv"
ROUTE_GEMM = "pallas_gemm"
ROUTE_XLA = "xla_ref"

DEFAULT_TABLE_PATH = Path(__file__).parent / "tuned_tiles.json"
TABLE_ENV = "REPRO_TUNED_TABLE"

# trace-time dispatch counters: route name, plus "<route>:padded|exact"
counters: collections.Counter = collections.Counter()

# trace-time plane-traffic accounting, kept separate from the route
# counters so route assertions stay stable.  Per packed_matmul trace:
#   "<route>:planes<P>"   — calls that streamed P of the 3 bit-planes
#   "plane_reads"         — plane-tiles streamed (planes touched x tiles)
#   "plane_words_read"    — int32 plane words the routed kernel streams
#   "plane_words_full"    — words a full 3-plane stream would have read
#   "mask_variants"       — mask variants a masked call unrolls
#   "mask_variants_suffix" — what MASK_VARIANTS[demand_drop:] would unroll
# read/full < 1 is exactly the demand-driven HBM saving on that trace;
# mask_variants/mask_variants_suffix < 1 is how often the leaf's tier plan
# prunes a variant that the demand floor alone keeps.
traffic: collections.Counter = collections.Counter()


def reset_counters() -> None:
    counters.clear()
    traffic.clear()


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One kernel tiling: which kernel, and its (bm, bk, bn) preferences."""

    kind: str  # "gemv" | "gemm"
    bm: int
    bk: int
    bn: int

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Plan:
    """A resolved dispatch: route + fitted tiles + padded problem shape."""

    route: str
    m: int
    k: int
    n: int
    pm: int  # padded M (== m when exact)
    pn: int  # padded N
    bm: int = 0
    bk: int = 0
    bn: int = 0

    @property
    def padded(self) -> bool:
        return (self.pm, self.pn) != (self.m, self.n)


# --------------------------------------------------------------------------
# Tuned-table IO
# --------------------------------------------------------------------------
_BUILTIN_CLASS_DEFAULTS = {
    "gemv": TileConfig(kind="gemv", bm=SUBLANE, bk=1024, bn=256),
    "gemm": TileConfig(kind="gemm", bm=256, bk=512, bn=256),
}

_TABLE: dict | None = None


def shape_key(m: int, k: int, n: int, g: int) -> str:
    return f"{m}x{k}x{n}g{g}"


def shape_class(m: int) -> str:
    return "gemv" if m <= GEMV_M_MAX else "gemm"


def load_tuned_table(path: str | Path | None = None) -> dict:
    """Read a dispatch table JSON: {backend: {key: {kind, bm, bk, bn}}}."""
    path = Path(path or os.environ.get(TABLE_ENV) or DEFAULT_TABLE_PATH)
    with open(path) as f:
        table = json.load(f)
    table.pop("version", None)
    return table


def save_tuned_table(table: dict, path: str | Path) -> Path:
    """Write a dispatch table JSON (inverse of :func:`load_tuned_table`)."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    out = {"version": 1}
    for backend, entries in table.items():
        out[backend] = {
            key: cfg.to_json() if isinstance(cfg, TileConfig) else dict(cfg)
            for key, cfg in entries.items()
        }
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def set_tuned_table(table: dict | str | Path | None) -> None:
    """Install a table override (dict or path); None re-reads the default."""
    global _TABLE
    if table is None:
        _TABLE = None
        return
    if isinstance(table, (str, Path)):
        table = load_tuned_table(table)
    _TABLE = dict(table)


def _table() -> dict:
    global _TABLE
    if _TABLE is None:
        try:
            _TABLE = load_tuned_table()
        except (OSError, json.JSONDecodeError):
            if os.environ.get(TABLE_ENV):
                # an explicit override that doesn't load is a config error,
                # not something to silently paper over with builtin tiles
                raise
            _TABLE = {}
    return _TABLE


def _resolve_config(m: int, k: int, n: int, g: int, backend: str) -> TileConfig:
    """(shape, backend) -> preferred TileConfig, deterministically."""
    entries = _table().get(backend, {})
    raw = entries.get(shape_key(m, k, n, g)) or entries.get(shape_class(m))
    if raw is not None:
        cfg = raw if isinstance(raw, TileConfig) else TileConfig(**raw)
    else:
        cfg = _BUILTIN_CLASS_DEFAULTS[shape_class(m)]
    if cfg.kind == "gemv" and m > GEMV_M_MAX:
        # a table can promote small-M shapes to GEMM, never the reverse:
        # the GEMV kernel keeps all of M in one block.
        cfg = dataclasses.replace(cfg, kind="gemm")
    return cfg


# --------------------------------------------------------------------------
# Tile fitting (with padding for ragged shapes)
# --------------------------------------------------------------------------
def _fit_dim(dim: int, pref: int, align: int) -> tuple[int, int]:
    """Fit a tile to ``dim``: returns (tile, padded_dim) with tile | padded.

    A dim at most ``pref`` is one whole block (no padding; a single
    unaligned block is masked by Mosaic).  Larger dims prefer an exact
    ``align``-multiple divisor (no padding); failing that, the
    ``align``-multiple tile at most ``pref`` that minimizes zero padding
    (ties to the larger tile), with ``dim`` padded up to it.
    """
    pref = max(pref, align)
    if dim <= pref:
        return dim, dim
    for t in range(pref, 0, -1):
        if dim % t == 0 and t % align == 0:
            return t, dim
    cands = range(align, pref + 1, align)
    tile = min(cands, key=lambda t: (-(-dim // t) * t, -t))
    return tile, -(-dim // tile) * tile


def _fit_k(k: int, pref: int, g: int) -> int:
    """K tile: the whole K, or the largest divisor of K <= pref that keeps
    every K-tiled block Mosaic-legal.  A split tile is the last dim of the
    x block (lane-aligned: a multiple of 128), and it sets the sublane dim
    of the plane block (bk//32) and of the scale block (bk//G), each a
    multiple of 8.  K is never padded (padding K would also mean
    fabricating scale rows), so a K with no such divisor is one tile."""
    if k <= pref:
        return k
    mult = math.lcm(LANE, PLANE * SUBLANE, g * SUBLANE)
    for t in range(pref // mult * mult, 0, -mult):
        if k % t == 0:
            return t
    return k


def plan(m: int, k: int, n: int, g: int, *, backend: str | None = None,
         use_kernel: bool = True) -> Plan:
    """Resolve (M, K, N, G, backend) to a concrete kernel plan."""
    if k % PLANE:
        raise ValueError(f"K={k} is not a multiple of the {PLANE}-code plane word")
    if k % g:
        raise ValueError(f"group_size={g} does not divide K={k}")
    if not use_kernel:
        return Plan(route=ROUTE_XLA, m=m, k=k, n=n, pm=m, pn=n)
    backend = backend or jax.default_backend()
    cfg = _resolve_config(m, k, n, g, backend)
    bk = _fit_k(k, cfg.bk, g)
    if cfg.kind == "gemv":
        pm = m if m % SUBLANE == 0 or m < SUBLANE else -(-m // SUBLANE) * SUBLANE
        bn, pn = _fit_dim(n, cfg.bn, LANE)
        return Plan(route=ROUTE_GEMV, m=m, k=k, n=n, pm=pm, pn=pn,
                    bm=pm, bk=bk, bn=bn)
    bm, pm = _fit_dim(m, cfg.bm, SUBLANE)
    bn, pn = _fit_dim(n, cfg.bn, LANE)
    return Plan(route=ROUTE_GEMM, m=m, k=k, n=n, pm=pm, pn=pn,
                bm=bm, bk=bk, bn=bn)


# --------------------------------------------------------------------------
# Execution
# --------------------------------------------------------------------------
def _pad_axis(a: jax.Array, axis: int, to: int) -> jax.Array:
    if a.shape[axis] == to:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, to - a.shape[axis])
    return jnp.pad(a, pads)


def _count_traffic(p: Plan, k: int, n_read: int) -> None:
    """Record plane-stream traffic for one routed call (trace-time)."""
    if p.route == ROUTE_GEMV:
        tiles = (p.pn // p.bn) * (k // p.bk)
    elif p.route == ROUTE_GEMM:
        tiles = (p.pm // p.bm) * (p.pn // p.bn) * (k // p.bk)
    else:
        tiles = 1
    words = k // PLANE * p.pn
    traffic[f"{p.route}:planes{n_read}"] += 1
    traffic["plane_reads"] += n_read * tiles
    traffic["plane_words_read"] += n_read * words
    traffic["plane_words_full"] += 3 * words


def packed_matmul(
    x: jax.Array,
    planes: jax.Array,
    scales: jax.Array,
    *,
    group_size: int,
    use_kernel: bool = True,
    interpret: bool | None = None,
    plane_mask: jax.Array | None = None,
    sign_mag: bool = False,
    plane_major: bool = False,
    demand_drop: int = 0,
    variants: tuple[int, ...] | None = None,
) -> jax.Array:
    """x (M,K) @ decode(planes (K//32,3,N), scales (K//G,N)) -> (M,N) f32.

    The one entry point every packed matmul goes through: plans on the
    static shapes, zero-pads ragged M/N to the fitted tile, runs the
    routed kernel, and slices the pad back off.  Never materializes the
    dense weight.

    ``plane_mask`` (M,) int32 — one 3-bit code mask per x row, values from
    :data:`ref.MASK_VARIANTS` — makes the matmul quality-tiered PER ROW: row m
    contracts against the weight decoded under its own mask, bit-identical
    to the unmasked matmul on ``truncate(drop_m)`` planes.  The mask is a
    traced operand split into a fixed variant activation stack, so a
    tier change is a data change (mask flip), never a retrace; plan/route
    and tile fitting are identical to the unmasked call.

    ``sign_mag`` selects the wire-v2 sign-magnitude decoder;
    ``plane_major`` marks ``planes`` as (3, K//32, N) MSB-first, the layout
    whose HBM read shortens with demand; ``demand_drop`` (static, 0..2) is
    the batch demand floor: every live row drops at least that many planes,
    so the kernel only streams the ``3 - demand_drop`` demanded planes
    (plane-major).  ``variants`` (static) is the masked call's variant
    set: an ordered subset of ``MASK_VARIANTS[demand_drop:]`` (the default)
    holding the masks some live row can select
    (``PackedWeight.mask_variants``).  Rows whose mask is not in it
    contribute zeros; the caller (engine demand vector and the leaf's tier
    plan) guarantees no live row has such a mask."""
    m, k = x.shape
    n = planes.shape[-1]
    if not 0 <= demand_drop < 3:
        raise ValueError(f"demand_drop must be 0..2, got {demand_drop}")
    if plane_mask is None and not plane_major:
        demand_drop = 0  # interleaved unmasked has nothing to prune
    p = plan(m, k, n, group_size, use_kernel=use_kernel)
    counters[p.route] += 1
    counters[f"{p.route}:{'padded' if p.padded else 'exact'}"] += 1
    # interleaved planes cannot shorten the read: all 3 planes stream.
    n_read = 3 - demand_drop if plane_major else 3
    _count_traffic(p, k, n_read)
    if plane_mask is not None:
        counters[f"{p.route}:masked"] += 1
        variants = ref.mask_variants(demand_drop, variants)
        traffic["mask_variants"] += len(variants)
        traffic["mask_variants_suffix"] += 3 - demand_drop
        # variant split: xs[i] keeps exactly the rows masked variants[i]
        # (a row matches one variant; others contribute exact zeros).  Pad
        # rows carry mask 0 and rows outside the set match no variant ->
        # exact zero rows.
        sel = jnp.stack([plane_mask == v for v in variants])
        xs = jnp.where(sel[:, :, None], x[None], 0).astype(x.dtype)

    if p.route == ROUTE_XLA:
        if plane_mask is not None:
            return ref.qsq_matmul_masked_ref(
                xs, planes, scales, group_size, sign_mag=sign_mag,
                plane_major=plane_major, demand_drop=demand_drop,
                variants=variants)
        return ref.qsq_matmul_ref(
            x, planes, scales, group_size, sign_mag=sign_mag,
            plane_major=plane_major, n_planes=3 - demand_drop)

    from repro.kernels import ops  # deferred: keeps pallas off cold paths

    pp = _pad_axis(planes, 2, p.pn)
    sp = _pad_axis(scales, 1, p.pn)
    if plane_mask is not None:
        xsp = _pad_axis(xs, 1, p.pm)
        if p.route == ROUTE_GEMV:
            out = ops.qsq_matvec_masked(xsp, pp, sp, group_size=group_size,
                                        bk=p.bk, bn=p.bn, interpret=interpret,
                                        sign_mag=sign_mag,
                                        plane_major=plane_major,
                                        demand_drop=demand_drop,
                                        variants=variants)
        else:
            out = ops.qsq_matmul_masked(xsp, pp, sp, group_size=group_size,
                                        bm=p.bm, bk=p.bk, bn=p.bn,
                                        interpret=interpret,
                                        sign_mag=sign_mag,
                                        plane_major=plane_major,
                                        demand_drop=demand_drop,
                                        variants=variants)
        return out[:m, :n] if p.padded else out

    xp = _pad_axis(x, 0, p.pm)
    if p.route == ROUTE_GEMV:
        out = ops.qsq_matvec(xp, pp, sp, group_size=group_size,
                             bk=p.bk, bn=p.bn, interpret=interpret,
                             sign_mag=sign_mag, plane_major=plane_major,
                             demand_drop=demand_drop)
    else:
        out = ops.qsq_matmul(xp, pp, sp, group_size=group_size,
                             bm=p.bm, bk=p.bk, bn=p.bn, interpret=interpret,
                             sign_mag=sign_mag, plane_major=plane_major,
                             demand_drop=demand_drop)
    return out[:m, :n] if p.padded else out
