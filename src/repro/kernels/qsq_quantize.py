"""Pallas TPU kernel: QSQ encode (Eq. 9 + nearest-level assignment).

Used by the checkpoint writer and the gradient compressor, where encode speed
matters (grads are encoded every step before the cross-pod all-reduce).

Layout:
  w       (K, N) f32/bf16   input weights/grads, grouped along K
  codes   (K, N) int32      Table II codes (packed to bit-planes by the caller;
                            int32 because TPU Pallas prefers 32-bit stores)
  scales  (K//G, N) f32     per-group scalars

Grid: (K//bk, N//bn).  bk must be a multiple of the group size so each block
owns whole groups (the reduction for alpha never crosses a block boundary).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qsq_quantize_kernel(w_ref, codes_ref, scales_ref, *, group_size: int, phi: int):
    bk, bn = w_ref.shape
    ng = bk // group_size
    w = w_ref[...].astype(jnp.float32).reshape(ng, group_size, bn)

    # Eq. 9: alpha = sum|w| / (phi * N) per group
    alpha = jnp.sum(jnp.abs(w), axis=1) / (phi * group_size)  # (ng, bn)
    safe = jnp.where(alpha == 0, 1.0, alpha)

    # nearest-level assignment over {0, +-1, +-2, +-4} capped by phi
    r = w / safe[:, None, :]
    a = jnp.abs(r)
    mag = jnp.where(a < 0.5, 0, jnp.where(a < 1.5, 1, jnp.where(a < 3.0, 2, 4)))
    max_level = {1: 1, 2: 2, 4: 4}[phi]
    mag = jnp.minimum(mag, max_level)
    # level -> Table II code: pos {1,2,4}->{1,2,3}; neg -> +3
    mag_idx = jnp.where(mag == 4, 3, mag)
    code = jnp.where(r < 0, jnp.where(mag_idx > 0, mag_idx + 3, 0), mag_idx)

    codes_ref[...] = code.reshape(bk, bn).astype(jnp.int32)
    scales_ref[...] = alpha.astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("group_size", "phi", "bk", "bn", "interpret")
)
def qsq_quantize(
    w: jax.Array,
    *,
    group_size: int,
    phi: int = 4,
    bk: int = 512,
    bn: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Encode w (K,N) -> (codes (K,N) int32, scales (K//G,N) f32)."""
    k, n = w.shape
    bk, bn = min(bk, k), min(bn, n)
    if k % bk or n % bn:
        raise ValueError(f"shape ({k},{n}) not divisible by tile ({bk},{bn})")
    if bk % group_size:
        raise ValueError(f"bk={bk} must be a multiple of group_size={group_size}")

    grid = (k // bk, n // bn)
    kernel = functools.partial(_qsq_quantize_kernel, group_size=group_size, phi=phi)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bk, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bk // group_size, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), jnp.int32),
            jax.ShapeDtypeStruct((k // group_size, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="qsq_quantize",
    )(w)
