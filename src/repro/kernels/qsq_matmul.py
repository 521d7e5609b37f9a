"""Pallas TPU kernel: fused QSQ dequant + matmul.

This is the paper's on-chip shift-and-scale decoder (Table II) realized for
TPU: weights live in HBM as 3-bit codes (bit-plane packed, 3 int32 words per
32 weights) plus one f32 scalar per group of G weights.  The kernel streams
code tiles into VMEM, unpacks them with shifts/masks in VREGs (the "decoder
hardware"), applies sign * 2^k * alpha (Table II rows as arithmetic), and
feeds the MXU — so dense f32/bf16 weights never touch HBM.

HBM traffic for weights drops from 16 bits/weight (bf16) to
3 + 32/G bits/weight (= 5 bits at G=16, 3.5 bits at G=64): a 3.2-4.6x cut in
the weight-streaming memory-roofline term, which dominates decode-shape
inference (measured by benchmarks/bench_kernels.py and
benchmarks/bench_serve.py; see README.md §Performance).

Layout (plane-interleaved, legacy):
  x       (M, K)            bf16/f32   activations
  planes  (K//32, 3, N)     int32      bit-plane packed 3-bit codes
  scales  (K//G, N)         f32        per-group scalars (group along K)
  out     (M, N)            f32

Layout (plane-major, ``plane_major=True``):
  planes  (3, K//32, N)     int32      MSB-first: plane 0 holds code bit 2

Plane-major is the demand-streaming layout: the planes a tier keeps are a
leading prefix, so a call that demands only ``n_planes`` planes reads a
``(n_planes, bk//32, bn)`` block — the dropped planes never leave HBM.
At n_planes=1 the weight stream is ~1/3 of the full read.

``sign_mag`` selects the wire-v2 sign-magnitude decoder (bit 2 = sign,
bits 1..0 = magnitude index) over the Table II offset decoder.

Grid: (M/bm, N/bn, K/bk), K innermost (accumulation, "arbitrary" semantics).
Default tile (bm=256, bk=512, bn=256) VMEM footprint:
  x 256x512xbf16 = 256 KiB, planes 16x3x256xi32 = 48 KiB,
  w-unpacked 512x256xf32 = 512 KiB, acc 256x256xf32 = 256 KiB
  => ~1.1 MiB/step, double-buffered ~2.2 MiB << 16 MiB VMEM.  All matmul
  dims are multiples of 128 (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import MASK_VARIANTS

PLANE = 32  # codes per bit-plane word (matches codec.PLANE_GROUP)


def _decode_codes(codes: jax.Array) -> jax.Array:
    """Table II: 3-bit code -> level value, as branch-free integer math.

    0->0, 1->+1, 2->+2, 3->+4, 4->-1, 5->-2, 6->-4, 7->0 (unused).
    """
    c = codes.astype(jnp.int32)
    pos = (c >= 1) & (c <= 3)
    neg = (c >= 4) & (c <= 6)
    # exponent: positive codes 1..3 -> 0..2; negative codes 4..6 -> 0..2
    exp = jnp.where(pos, c - 1, jnp.where(neg, c - 4, 0))
    mag = jnp.int32(1) << exp
    return jnp.where(pos, mag, jnp.where(neg, -mag, 0))


def _decode_codes_sm(codes: jax.Array) -> jax.Array:
    """Sign-magnitude (wire v2): bit 2 = sign, bits 1..0 = magnitude index.

    0->0, 1->+1, 2->+2, 3->+4, 4->-0 (=0), 5->-1, 6->-2, 7->-4.
    """
    c = codes.astype(jnp.int32)
    mag_idx = c & 3
    mag = jnp.int32(1) << jnp.maximum(mag_idx - 1, 0)
    val = jnp.where(mag_idx > 0, mag, 0)
    return jnp.where(c >= 4, -val, val)


def _decoder(sign_mag: bool):
    return _decode_codes_sm if sign_mag else _decode_codes


def _unpack_planes(planes_blk: jax.Array, bk: int, bn: int) -> jax.Array:
    """(bk//32, 3, bn) int32 interleaved bit-planes -> (bk, bn) int32 codes."""
    g = bk // PLANE
    # bit position j within each 32-code word, as an iota over a new axis
    j = jax.lax.broadcasted_iota(jnp.int32, (g, PLANE, bn), dimension=1)
    code = jnp.zeros((g, PLANE, bn), dtype=jnp.int32)
    for p in range(3):
        word = planes_blk[:, p, :]  # (g, bn)
        bit = (jax.lax.shift_right_logical(word[:, None, :], j)) & 1
        code = code | (bit << p)
    return code.reshape(bk, bn)


def _unpack_planes_major(
    planes_blk: jax.Array, bk: int, bn: int, n_planes: int
) -> jax.Array:
    """(n_planes, bk//32, bn) MSB-first plane-major words -> (bk, bn) codes.

    Streamed plane p carries code bit (2 - p); absent trailing planes
    contribute zero bits, exactly like a masked code stream.
    """
    g = bk // PLANE
    j = jax.lax.broadcasted_iota(jnp.int32, (g, PLANE, bn), dimension=1)
    code = jnp.zeros((g, PLANE, bn), dtype=jnp.int32)
    for p in range(n_planes):
        word = planes_blk[p]  # (g, bn)
        bit = (jax.lax.shift_right_logical(word[:, None, :], j)) & 1
        code = code | (bit << (2 - p))
    return code.reshape(bk, bn)


def _unpack(planes_blk, bk, bn, plane_major: bool, n_planes: int):
    if plane_major:
        return _unpack_planes_major(planes_blk, bk, bn, n_planes)
    return _unpack_planes(planes_blk, bk, bn)


def _planes_spec(plane_major: bool, n_planes: int, bk: int, bn: int):
    """Weight-plane BlockSpec for a (j-N, k-K) or (i-M, j-N, k-K) grid.

    Plane-major pins the plane axis at block row 0 with a block of only the
    demanded ``n_planes`` planes — the HBM read shortens with demand."""
    if plane_major:
        return (n_planes, bk // PLANE, bn), lambda *ids: (0, ids[-1], ids[-2])
    return (bk // PLANE, 3, bn), lambda *ids: (ids[-1], 0, ids[-2])


def _check_planes_shape(planes, kdim, n, plane_major):
    want = (3, kdim // PLANE, n) if plane_major else (kdim // PLANE, 3, n)
    if planes.shape != want:
        raise ValueError(f"planes shape {planes.shape} != {want}")


def _qsq_matmul_kernel(
    x_ref, planes_ref, scales_ref, o_ref, *,
    bk: int, group_size: int, sign_mag: bool, plane_major: bool, n_planes: int,
):
    bn = o_ref.shape[1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    codes = _unpack(planes_ref[...], bk, bn, plane_major, n_planes)
    levels = _decoder(sign_mag)(codes).astype(jnp.float32)   # (bk, bn)
    # broadcast per-group scales down each K-group of rows
    ng = bk // group_size
    lev_g = levels.reshape(ng, group_size, bn)
    w = (lev_g * scales_ref[...][:, None, :]).reshape(bk, bn)
    w = w.astype(x_ref.dtype)
    o_ref[...] += jnp.dot(
        x_ref[...], w, preferred_element_type=jnp.float32
    )


def _qsq_matmul_masked_kernel(
    xs_ref, planes_ref, scales_ref, o_ref, *,
    bk: int, group_size: int, sign_mag: bool, plane_major: bool,
    demand_drop: int,
):
    """Per-row plane-masked GEMM tile (see qsq_matvec._qsq_matvec_masked_kernel
    for the variant-split contract): one weight-tile stream, one static mask
    decode in VREGs per demanded variant, one dot per variant into the shared
    output.  ``demand_drop`` prunes the variants no live row can select."""
    bn = o_ref.shape[1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    codes = _unpack(planes_ref[...], bk, bn, plane_major, 3 - demand_drop)
    decode = _decoder(sign_mag)
    ng = bk // group_size
    sc = scales_ref[...]
    acc = None
    for i, mask in enumerate(MASK_VARIANTS[demand_drop:]):
        levels = decode(codes & mask).astype(jnp.float32)
        w = (levels.reshape(ng, group_size, bn) * sc[:, None, :]).reshape(bk, bn)
        d = jnp.dot(
            xs_ref[i], w.astype(xs_ref.dtype), preferred_element_type=jnp.float32
        )
        acc = d if acc is None else acc + d
    o_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "bm", "bk", "bn", "interpret",
                     "sign_mag", "plane_major", "demand_drop"),
)
def qsq_matmul_masked(
    xs: jax.Array,
    planes: jax.Array,
    scales: jax.Array,
    *,
    group_size: int,
    bm: int = 256,
    bk: int = 512,
    bn: int = 256,
    interpret: bool = False,
    sign_mag: bool = False,
    plane_major: bool = False,
    demand_drop: int = 0,
) -> jax.Array:
    """Plane-masked sibling of :func:`qsq_matmul`:
    xs (3 - demand_drop, M, K) -> (M, N) f32.

    xs[i] holds the x rows whose plane mask is
    ``ref.MASK_VARIANTS[demand_drop + i]`` (other rows zero).  Same tiling
    contract as the unmasked kernel.  With ``plane_major`` the weight block
    only spans the ``3 - demand_drop`` demanded planes."""
    nv, m, kdim = xs.shape
    n = planes.shape[-1]
    if not 0 <= demand_drop <= 2:
        raise ValueError(f"demand_drop must be 0..2, got {demand_drop}")
    n_planes = 3 - demand_drop
    if nv != n_planes:
        raise ValueError(
            f"xs leading dim {nv} != {n_planes} demanded mask variants")
    _check_planes_shape(planes, kdim, n, plane_major)
    if scales.shape != (kdim // group_size, n):
        raise ValueError(f"scales shape {scales.shape} != {(kdim // group_size, n)}")
    bm, bk, bn = min(bm, m), min(bk, kdim), min(bn, n)
    if m % bm or kdim % bk or n % bn:
        raise ValueError(f"shape ({m},{kdim},{n}) not divisible by tile ({bm},{bk},{bn})")
    if bk % PLANE or bk % group_size:
        raise ValueError(f"bk={bk} must be a multiple of 32 and group_size={group_size}")

    grid = (m // bm, n // bn, kdim // bk)
    kernel = functools.partial(
        _qsq_matmul_masked_kernel, bk=bk, group_size=group_size,
        sign_mag=sign_mag, plane_major=plane_major, demand_drop=demand_drop)
    pshape, pmap = _planes_spec(plane_major, n_planes, bk, bn)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nv, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec(pshape, pmap),
            pl.BlockSpec((bk // group_size, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xs, planes, scales)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "bm", "bk", "bn", "interpret",
                     "sign_mag", "plane_major", "demand_drop"),
)
def qsq_matmul(
    x: jax.Array,
    planes: jax.Array,
    scales: jax.Array,
    *,
    group_size: int,
    bm: int = 256,
    bk: int = 512,
    bn: int = 256,
    interpret: bool = False,
    sign_mag: bool = False,
    plane_major: bool = False,
    demand_drop: int = 0,
) -> jax.Array:
    """Fused 3-bit dequant + matmul: x (M,K) @ decode(planes, scales) -> (M,N) f32."""
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
    bm, bk, bn = min(bm, m), min(bk, kdim), min(bn, n)
    if m % bm or kdim % bk or n % bn:
        raise ValueError(f"shape ({m},{kdim},{n}) not divisible by tile ({bm},{bk},{bn})")
    if bk % PLANE or bk % group_size:
        raise ValueError(f"bk={bk} must be a multiple of 32 and group_size={group_size}")

    grid = (m // bm, n // bn, kdim // bk)
    kernel = functools.partial(
        _qsq_matmul_kernel, bk=bk, group_size=group_size,
        sign_mag=sign_mag, plane_major=plane_major, n_planes=n_planes)
    pshape, pmap = _planes_spec(plane_major, n_planes, bk, bn)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec(pshape, pmap),
            pl.BlockSpec((bk // group_size, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, planes, scales)
