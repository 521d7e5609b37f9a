"""Public jit'd entry points for the Pallas kernels.

On CPU (this container) the kernels run in interpret mode; on TPU they
compile to Mosaic.  ``auto_interpret()`` picks per-backend so the same code
path works in tests, benchmarks and the real launcher.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import codec
from repro.kernels import ref
from repro.kernels.qsq_matmul import qsq_matmul as _qsq_matmul_pallas
from repro.kernels.qsq_matmul import qsq_matmul_masked as _qsq_matmul_masked_pallas
from repro.kernels.qsq_matvec import qsq_matvec as _qsq_matvec_pallas
from repro.kernels.qsq_matvec import qsq_matvec_masked as _qsq_matvec_masked_pallas
from repro.kernels.qsq_quantize import qsq_quantize as _qsq_quantize_pallas


def auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def qsq_matmul(
    x: jax.Array,
    planes: jax.Array,
    scales: jax.Array,
    *,
    group_size: int,
    bm: int = 256,
    bk: int = 512,
    bn: int = 256,
    interpret: bool | None = None,
    use_pallas: bool = True,
    sign_mag: bool = False,
    plane_major: bool = False,
    demand_drop: int = 0,
) -> jax.Array:
    """x @ dequant(planes, scales).  Falls back to the XLA ref when asked."""
    if not use_pallas:
        return ref.qsq_matmul_ref(x, planes, scales, group_size,
                                  sign_mag=sign_mag, plane_major=plane_major,
                                  n_planes=3 - demand_drop)
    if interpret is None:
        interpret = auto_interpret()
    return _qsq_matmul_pallas(
        x, planes, scales, group_size=group_size, bm=bm, bk=bk, bn=bn,
        interpret=interpret, sign_mag=sign_mag, plane_major=plane_major,
        demand_drop=demand_drop,
    )


def qsq_matvec(
    x: jax.Array,
    planes: jax.Array,
    scales: jax.Array,
    *,
    group_size: int,
    bk: int = 1024,
    bn: int = 256,
    interpret: bool | None = None,
    use_pallas: bool = True,
    sign_mag: bool = False,
    plane_major: bool = False,
    demand_drop: int = 0,
) -> jax.Array:
    """Small-M x @ dequant(planes, scales) — the decode-shape GEMV kernel."""
    if not use_pallas:
        return ref.qsq_matmul_ref(x, planes, scales, group_size,
                                  sign_mag=sign_mag, plane_major=plane_major,
                                  n_planes=3 - demand_drop)
    if interpret is None:
        interpret = auto_interpret()
    return _qsq_matvec_pallas(
        x, planes, scales, group_size=group_size, bk=bk, bn=bn,
        interpret=interpret, sign_mag=sign_mag, plane_major=plane_major,
        demand_drop=demand_drop,
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
    interpret: bool | None = None,
    use_pallas: bool = True,
    sign_mag: bool = False,
    plane_major: bool = False,
    demand_drop: int = 0,
    variants: tuple[int, ...] | None = None,
) -> jax.Array:
    """Per-row plane-masked GEMM: xs (len(variants), M, K) activations
    split by mask variant (``ref.mask_variants``)."""
    if not use_pallas:
        return ref.qsq_matmul_masked_ref(xs, planes, scales, group_size,
                                         sign_mag=sign_mag,
                                         plane_major=plane_major,
                                         demand_drop=demand_drop,
                                         variants=variants)
    if interpret is None:
        interpret = auto_interpret()
    return _qsq_matmul_masked_pallas(
        xs, planes, scales, group_size=group_size, bm=bm, bk=bk, bn=bn,
        interpret=interpret, sign_mag=sign_mag, plane_major=plane_major,
        demand_drop=demand_drop, variants=variants,
    )


def qsq_matvec_masked(
    xs: jax.Array,
    planes: jax.Array,
    scales: jax.Array,
    *,
    group_size: int,
    bk: int = 1024,
    bn: int = 256,
    interpret: bool | None = None,
    use_pallas: bool = True,
    sign_mag: bool = False,
    plane_major: bool = False,
    demand_drop: int = 0,
    variants: tuple[int, ...] | None = None,
) -> jax.Array:
    """Per-row plane-masked GEMV: xs (len(variants), M, K) activations
    split by mask variant (``ref.mask_variants``)."""
    if not use_pallas:
        return ref.qsq_matmul_masked_ref(xs, planes, scales, group_size,
                                         sign_mag=sign_mag,
                                         plane_major=plane_major,
                                         demand_drop=demand_drop,
                                         variants=variants)
    if interpret is None:
        interpret = auto_interpret()
    return _qsq_matvec_masked_pallas(
        xs, planes, scales, group_size=group_size, bk=bk, bn=bn,
        interpret=interpret, sign_mag=sign_mag, plane_major=plane_major,
        demand_drop=demand_drop, variants=variants,
    )


def qsq_quantize(
    w: jax.Array,
    *,
    group_size: int,
    phi: int = 4,
    interpret: bool | None = None,
    use_pallas: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Encode a (K, N) tensor -> (codes uint8 (K,N), scales (K//G, N))."""
    if not use_pallas:
        return ref.qsq_quantize_ref(w, group_size, phi)
    if interpret is None:
        interpret = auto_interpret()
    codes_i32, scales = _qsq_quantize_pallas(
        w, group_size=group_size, phi=phi, interpret=interpret
    )
    return codes_i32.astype(jnp.uint8), scales


def pack_weight(w: jax.Array, *, group_size: int, phi: int = 4, **kw):
    """One-call helper: dense weight -> (bit-planes, scales) for qsq_matmul."""
    codes, scales = qsq_quantize(w, group_size=group_size, phi=phi, **kw)
    return codec.pack_bitplane(codes), scales
