"""Pallas TPU kernel: fused QSQ dequant + small-M matmul (decode GEMV).

``qsq_matmul`` tiles all three dims for the MXU, which is right for
prefill/train GEMMs but wasteful at decode shapes: with M = 8 batch slots a
256-row M tile is 97% padding, and the (i, j, k) grid re-reads the output
block every K step.  This kernel is the GEMV specialization the dispatcher
(`kernels/dispatch.py`) routes small-M matmuls to:

* the whole (small) M extent lives in one block — no M grid dim, no M
  padding beyond the 8-row sublane;
* the grid is (N, K) with K innermost ("arbitrary"), accumulating into a
  **VMEM scratch accumulator** that is written back to the output exactly
  once, on the last K step — the output block is never re-streamed;
* scales are folded into the plane unpack (one multiply on the decoded
  levels while they are still in VREGs), so the weight tile goes bits ->
  levels -> scaled f32 without a dense round-trip;
* tiles default to GEMV proportions (deep K, modest N) instead of the
  square 256x512x256 GEMM config — the weight stream, not the MXU, is the
  roofline term at M <= 16.

Layout matches qsq_matmul: x (M, K), planes (K//32, 3, N) int32 (or
(3, K//32, N) when ``plane_major``), scales (K//G, N) f32 -> out (M, N)
f32.  ``sign_mag``/``plane_major``/``demand_drop`` follow the qsq_matmul
contract; since decode is weight-stream bound, demand-shortened plane-major
reads cut the dominant roofline term almost linearly in planes demanded.

The per-row masked sibling takes x pre-split into one slice per mask
variant it unrolls: a static, ordered subset of
``MASK_VARIANTS[demand_drop:]`` that holds only the masks some live row
can select.  It unpacks each weight tile once and builds every variant's
weight from the shared bits, so a variant costs a few VPU ops and one MXU
pass, not a whole decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.qsq_matmul import (
    PLANE,
    _check_planes_shape,
    _decoder,
    _masked_dot,
    _planes_spec,
    _unpack,
)
from repro.kernels.ref import mask_variants


def _qsq_matvec_kernel(
    x_ref, planes_ref, scales_ref, o_ref, acc_ref, *,
    bk: int, group_size: int, nk: int, sign_mag: bool, plane_major: bool,
    n_planes: int,
):
    bn = o_ref.shape[1]
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack(planes_ref[...], bk, bn, plane_major, n_planes)
    # scales folded into the unpack: levels scale while still in VREGs
    levels = _decoder(sign_mag)(codes).astype(jnp.float32)
    ng = bk // group_size
    w = (levels.reshape(ng, group_size, bn)
         * scales_ref[...][:, None, :]).reshape(bk, bn)
    acc_ref[...] += jnp.dot(
        x_ref[...], w.astype(x_ref.dtype), preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _qsq_matvec_masked_kernel(
    xs_ref, planes_ref, scales_ref, o_ref, acc_ref, *,
    bk: int, group_size: int, nk: int, sign_mag: bool, plane_major: bool,
    demand_drop: int, variants: tuple[int, ...],
):
    """Per-row plane-masked GEMV: xs_ref (len(variants), M, bk) carries x
    pre-split by mask variant (rows of other variants zeroed).  The weight
    tile streams ONCE and is unpacked once; each static mask of
    ``variants`` then gets its weight from the shared bits
    (``qsq_matmul._masked_weights``) and contracts its own x rows.  A row's
    accumulator only ever receives its variant's product plus exact zeros,
    so per-row output is bit-identical to the unmasked kernel on
    plane-truncated weights (-0 and +0 aside).  ``variants`` holds only the
    masks some live row can select; with ``plane_major`` the streamed
    weight block also shrinks to the ``3 - demand_drop`` demanded
    planes."""
    bn = o_ref.shape[1]
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _masked_dot(
        xs_ref, planes_ref[...], scales_ref[...], variants, bk=bk, bn=bn,
        group_size=group_size, sign_mag=sign_mag, plane_major=plane_major,
        n_planes=3 - demand_drop)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("group_size", "bk", "bn", "interpret",
                              "sign_mag", "plane_major", "demand_drop",
                              "variants")
)
def qsq_matvec_masked(
    xs: jax.Array,
    planes: jax.Array,
    scales: jax.Array,
    *,
    group_size: int,
    bk: int = 1024,
    bn: int = 256,
    interpret: bool = False,
    sign_mag: bool = False,
    plane_major: bool = False,
    demand_drop: int = 0,
    variants: tuple[int, ...] | None = None,
) -> jax.Array:
    """Plane-masked sibling of :func:`qsq_matvec`:
    xs (len(variants), M, K) -> (M, N).

    ``variants`` (static; default ``MASK_VARIANTS[demand_drop:]``) is an
    ordered subset of that suffix, and xs[i] holds the x rows whose plane
    mask is ``variants[i]`` (other rows zero); the dispatcher builds it
    from the per-row plane_mask operand.  Same tiling contract as the
    unmasked kernel."""
    nv, m, kdim = xs.shape
    n = planes.shape[-1]
    if not 0 <= demand_drop <= 2:
        raise ValueError(f"demand_drop must be 0..2, got {demand_drop}")
    n_planes = 3 - demand_drop
    variants = mask_variants(demand_drop, variants)
    if nv != len(variants):
        raise ValueError(f"xs leading dim {nv} != {len(variants)} mask variants")
    _check_planes_shape(planes, kdim, n, plane_major)
    if scales.shape != (kdim // group_size, n):
        raise ValueError(f"scales shape {scales.shape} != {(kdim // group_size, n)}")
    bk, bn = min(bk, kdim), min(bn, n)
    if kdim % bk or n % bn:
        raise ValueError(f"shape ({m},{kdim},{n}) not divisible by tile (bk={bk},bn={bn})")
    if bk % PLANE or bk % group_size:
        raise ValueError(f"bk={bk} must be a multiple of 32 and group_size={group_size}")

    nk = kdim // bk
    grid = (n // bn, nk)
    kernel = functools.partial(
        _qsq_matvec_masked_kernel, bk=bk, group_size=group_size, nk=nk,
        sign_mag=sign_mag, plane_major=plane_major, demand_drop=demand_drop,
        variants=variants,
    )
    pshape, pmap = _planes_spec(plane_major, n_planes, bk, bn)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nv, m, bk), lambda j, k: (0, 0, k)),
            pl.BlockSpec(pshape, pmap),
            pl.BlockSpec((bk // group_size, bn), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="qsq_matvec_masked",
    )(xs, planes, scales)


@functools.partial(
    jax.jit, static_argnames=("group_size", "bk", "bn", "interpret",
                              "sign_mag", "plane_major", "demand_drop")
)
def qsq_matvec(
    x: jax.Array,
    planes: jax.Array,
    scales: jax.Array,
    *,
    group_size: int,
    bk: int = 1024,
    bn: int = 256,
    interpret: bool = False,
    sign_mag: bool = False,
    plane_major: bool = False,
    demand_drop: int = 0,
) -> jax.Array:
    """Small-M fused 3-bit dequant matmul: x (M,K) @ decode(planes, scales).

    The full M extent is one block; callers (the dispatcher) keep M small
    (decode shapes) and pad/tile K, N so ``bk | K`` and ``bn | N``.
    """
    m, kdim = x.shape
    n = planes.shape[-1]
    if not 0 <= demand_drop <= 2:
        raise ValueError(f"demand_drop must be 0..2, got {demand_drop}")
    if demand_drop and not plane_major:
        raise ValueError("demand_drop requires the plane-major layout")
    n_planes = 3 - demand_drop
    _check_planes_shape(planes, kdim, n, plane_major)
    if scales.shape != (kdim // group_size, n):
        raise ValueError(f"scales shape {scales.shape} != {(kdim // group_size, n)}")
    bk, bn = min(bk, kdim), min(bn, n)
    if kdim % bk or n % bn:
        raise ValueError(f"shape ({m},{kdim},{n}) not divisible by tile (bk={bk},bn={bn})")
    if bk % PLANE or bk % group_size:
        raise ValueError(f"bk={bk} must be a multiple of 32 and group_size={group_size}")

    nk = kdim // bk
    grid = (n // bn, nk)
    kernel = functools.partial(
        _qsq_matvec_kernel, bk=bk, group_size=group_size, nk=nk,
        sign_mag=sign_mag, plane_major=plane_major, n_planes=n_planes
    )
    pshape, pmap = _planes_spec(plane_major, n_planes, bk, bn)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, k: (0, k)),
            pl.BlockSpec(pshape, pmap),
            pl.BlockSpec((bk // group_size, bn), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="qsq_matvec",
    )(x, planes, scales)
