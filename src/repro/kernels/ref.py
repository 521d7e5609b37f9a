"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth the kernels are validated against (interpret=True
on CPU, real compile on TPU).  They are deliberately written with the
simplest possible jnp — no tiling, no cleverness.

Two code formats share the 3-bit planes: Table II offset codes (legacy,
``sign_mag=False``) and sign-magnitude codes (wire v2, ``sign_mag=True``).
Two physical layouts: plane-interleaved ``(K//32, 3, N)`` (legacy) and
plane-major ``(3, K//32, N)`` MSB-first, where a demand-dropped trailing
plane is simply never read (``demand_drop``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import codec
from repro.core.qsq import codes_to_levels, levels_to_codes, smcodes_to_levels

# The three plane masks a quality tier can put on a row: keep all 3 code
# planes, drop the LSB plane, drop the two LSB planes (drop = 0, 1, 2).
# Fixed and ordered, so masked kernels unroll over them statically — a
# per-row tier change is a data change, never a retrace.  A masked call
# unrolls a static subset ``variants``, in this order: at most the suffix
# ``MASK_VARIANTS[demand_drop:]`` (with every live row at drop >= d, the
# first d variants are provably dead), and on a leaf with a tier vector
# only the masks its tiers at or above the demand floor select
# (``PackedWeight.mask_variants``).  A row whose mask is outside the set
# reads exact zeros.
MASK_VARIANTS = (0b111, 0b110, 0b100)


def mask_variants(demand_drop: int,
                  variants: tuple[int, ...] | None = None) -> tuple[int, ...]:
    """The static variant set of a masked call: ``variants`` checked to be
    a non-empty, ordered subset of ``MASK_VARIANTS[demand_drop:]``, or
    that whole suffix when ``variants`` is None."""
    suffix = MASK_VARIANTS[demand_drop:]
    if variants is None:
        return suffix
    variants = tuple(variants)
    if not variants or variants != tuple(v for v in suffix if v in variants):
        raise ValueError(f"mask variants {variants} are not an ordered "
                         f"subset of {suffix} (demand_drop={demand_drop})")
    return variants


def _unpack_codes(planes: jax.Array, plane_major: bool, n_planes: int = 3):
    """Planes in either layout -> (K, N) uint8 codes.

    For plane-major input only the leading ``n_planes`` planes are read —
    the XLA mirror of the shortened HBM stream.
    """
    if plane_major:
        return codec.unpack_bitplane_major(planes[:n_planes])
    return codec.unpack_bitplane(planes)


def _decode(codes: jax.Array, sign_mag: bool) -> jax.Array:
    return (smcodes_to_levels(codes) if sign_mag
            else codes_to_levels(codes)).astype(jnp.float32)


def qsq_dequant_ref(
    planes: jax.Array, scales: jax.Array, group_size: int, *,
    sign_mag: bool = False, plane_major: bool = False, n_planes: int = 3,
) -> jax.Array:
    """Bit-plane packed codes + per-group scales -> dense f32 weights.

    planes: (K//32, 3, N) int32 (or (3, K//32, N) plane-major),
    scales: (K//G, N) f32 -> (K, N) f32.
    """
    codes = _unpack_codes(planes, plane_major, n_planes)  # (K, N) uint8
    levels = _decode(codes, sign_mag)  # (K, N)
    k = levels.shape[0]
    lev_g = levels.reshape(k // group_size, group_size, *levels.shape[1:])
    w = lev_g * scales[:, None]
    return w.reshape(levels.shape)


def qsq_matmul_ref(
    x: jax.Array, planes: jax.Array, scales: jax.Array, group_size: int, *,
    sign_mag: bool = False, plane_major: bool = False, n_planes: int = 3,
) -> jax.Array:
    """x (M,K) @ dequant(planes, scales) (K,N) -> (M,N) f32."""
    w = qsq_dequant_ref(planes, scales, group_size, sign_mag=sign_mag,
                        plane_major=plane_major, n_planes=n_planes)
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)


def qsq_dequant_masked_ref(
    planes: jax.Array, scales: jax.Array, group_size: int, code_mask: int, *,
    sign_mag: bool = False, plane_major: bool = False, n_planes: int = 3,
) -> jax.Array:
    """Dequant with ``code_mask`` ANDed onto every 3-bit code first.

    ``decode(codes & mask)`` on full-quality planes is bit-identical to a
    plain decode of planes whose dropped LSB words were zeroed
    (``PackedWeight.truncate``): zeroing a plane word and masking the
    corresponding code bit are the same operation on the code stream.
    """
    codes = _unpack_codes(planes, plane_major, n_planes)  # (K, N) uint8
    levels = _decode(codes & code_mask, sign_mag)
    k = levels.shape[0]
    lev_g = levels.reshape(k // group_size, group_size, *levels.shape[1:])
    w = lev_g * scales[:, None]
    return w.reshape(levels.shape)


def qsq_matmul_masked_ref(
    xs: jax.Array, planes: jax.Array, scales: jax.Array, group_size: int, *,
    sign_mag: bool = False, plane_major: bool = False, demand_drop: int = 0,
    variants: tuple[int, ...] | None = None,
) -> jax.Array:
    """Per-row plane-masked matmul: xs (len(variants), M, K) -> (M, N) f32.

    ``variants`` (static, default ``MASK_VARIANTS[demand_drop:]``; see
    :func:`mask_variants`) lists the masks the call unrolls, and ``xs[i]``
    holds the rows of x whose plane mask is ``variants[i]`` (all other rows
    zeroed).  Each variant contracts against the weight decoded under that
    mask; a row's result is exactly its variant's term because the other
    variants contribute exact zeros — so row m equals
    ``x[m] @ dequant(truncate(drop_m))`` bit for bit.  With
    ``demand_drop > 0`` on plane-major planes only ``3 - demand_drop``
    planes are ever unpacked: the demand-shortened read.
    """
    n_planes = 3 - demand_drop
    out = None
    for i, mask in enumerate(mask_variants(demand_drop, variants)):
        w = qsq_dequant_masked_ref(
            planes, scales, group_size, mask, sign_mag=sign_mag,
            plane_major=plane_major, n_planes=n_planes)
        d = jnp.dot(xs[i], w.astype(xs.dtype), preferred_element_type=jnp.float32)
        out = d if out is None else out + d
    return out


def qsq_quantize_ref(
    w: jax.Array, group_size: int, phi: int
) -> tuple[jax.Array, jax.Array]:
    """Nearest-level QSQ encode -> (codes (K,N) uint8, scales (K//G,N) f32).

    Matches repro.core.qsq.quantize(assign="nearest") exactly.
    """
    from repro.core.qsq import QSQConfig, quantize

    q = quantize(w, QSQConfig(phi=phi, group_size=group_size, assign="nearest"))
    return levels_to_codes(q.levels), q.scales
