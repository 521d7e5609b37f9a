"""Seeded weights that lie exactly on the 3-bit QSQ grid.

Every quantized leaf is drawn as ``sign * level * alpha``: a level from
{0, 1, 2, 4} per weight and one bf16 scale ``alpha`` per group of
``group`` weights along the leaf's grouping axis.  Each group's level sum
is kept at or above ``ceil(2.75 * group)``, so the quantizer's first scale
(sum |w| / (4 * group), QSQ Eq. 9) divides every weight into exactly its
level with a margin of at least 3% to the nearest rounding threshold, and
the least-squares refit then returns ``alpha`` itself.  Quantizing these
weights is therefore lossless, and the benchmark knows the served model
exactly without reading anything the program made: the reference uses the
same draws, and a quality tier that drops the least significant code
plane maps each level to ``{0: 0, 1: 0, 2: 2, 4: 2}`` (sign-magnitude
codes lose bit 0 of the magnitude index).

Randomness is keyed by the seed and the leaf's path, so one leaf can be
drawn alone (the reference does, leaf by leaf) and gives the same values
as the whole tree drawn in one jitted call.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

# P(level magnitude = 0, 1, 2, 4) before the group floor lifts low groups
LEVEL_P = (0.10, 0.15, 0.30, 0.45)
MAGS = (0, 1, 2, 4)
ALPHA_SPREAD = 0.25  # log-normal spread of the group scales around s


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """One parameter leaf: its shape and dtype, and how it is drawn.

    ``init`` is ``grid`` (QSQ-grid weights of standard deviation about
    ``std``, grouped in runs of ``group`` along ``group_axis``) or
    ``ones`` (norm scales)."""

    shape: tuple
    dtype: str
    init: str
    std: float = 0.0
    group_axis: int = 0
    group: int = 16


def group_floor(group: int) -> int:
    """Least level sum of a group that the quantizer maps back exactly."""
    return math.ceil(2.75 * group)


def leaf_key(seed: int, path: str) -> jax.Array:
    """Key of one leaf: the run seed folded with a hash of the path."""
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(zlib.crc32(path.encode())))


def drop_levels(mag: jax.Array, drop: int) -> jax.Array:
    """Level magnitudes after ``drop`` least significant code planes are
    cleared (sign-magnitude code: magnitude index 0..3 = level 0, 1, 2, 4)."""
    if drop == 0:
        return mag
    idx = jnp.where(mag == 4, 3, mag)
    idx = idx & ((0b11 << drop) & 0b11)
    return jnp.where(idx == 3, 4, idx)


def _grid(key: jax.Array, spec: LeafSpec, drop: int) -> jax.Array:
    shape = tuple(spec.shape)
    ax, g = spec.group_axis, spec.group
    moved = shape[:ax] + shape[ax + 1:] + (shape[ax],)
    gshape = moved[:-1] + (moved[-1] // g, g)
    k_mag, k_sign, k_alpha = jax.random.split(key, 3)
    u = jax.random.uniform(k_mag, gshape)
    cum = np.cumsum(LEVEL_P)
    mag = jnp.where(u < cum[0], 0, jnp.where(u < cum[1], 1,
                                             jnp.where(u < cum[2], 2, 4)))
    mag = mag.astype(jnp.int32)
    floor = group_floor(g)
    low = jnp.sum(mag, -1, keepdims=True) < floor
    mag = jnp.where(low & (mag < 2), 2, mag)  # lift 0 and 1 to 2
    low = jnp.sum(mag, -1, keepdims=True) < floor
    lead = jnp.arange(g) < -(-(floor - 2 * g) // 2)
    mag = jnp.where(low & lead, 4, mag)  # then the first few to 4
    sign = jnp.where(jax.random.bernoulli(k_sign, 0.5, gshape), -1.0, 1.0)
    mean_sq = jnp.mean(mag.astype(jnp.float32) ** 2)
    s = spec.std / jnp.sqrt(mean_sq * math.exp(2 * ALPHA_SPREAD ** 2))
    z = jax.random.normal(k_alpha, gshape[:-1] + (1,))
    alpha = (s * jnp.exp(ALPHA_SPREAD * z)).astype(jnp.bfloat16)
    w = sign * drop_levels(mag, drop).astype(jnp.float32) * alpha.astype(
        jnp.float32)
    w = w.reshape(moved)
    return jnp.moveaxis(w, -1, ax).astype(spec.dtype)


def draw_leaf(seed: int, path: str, spec: LeafSpec, drop: int = 0) -> jax.Array:
    """One leaf, on the default device, in its served dtype.  ``drop``
    clears that many low code planes of a grid leaf (a quality tier)."""
    return _draw_jit(leaf_key(seed, path), spec, drop)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw_jit(key, spec: LeafSpec, drop: int):
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    return _grid(key, spec, drop)


def draw_tree(seed: int, specs: dict[str, LeafSpec]) -> dict:
    """Every leaf of ``specs`` ({'a/b': spec}) as a nested dict, drawn in
    one jitted call on the default device."""
    paths = sorted(specs)
    keys = [leaf_key(seed, p) for p in paths]

    @jax.jit
    def make(keys):
        return [_draw_jit(k, specs[p], 0)
                for k, p in zip(keys, paths, strict=True)]

    return nest(dict(zip(paths, make(keys), strict=True)))


def nest(flat: dict) -> dict:
    """{'a/b': x} -> {'a': {'b': x}}."""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out
