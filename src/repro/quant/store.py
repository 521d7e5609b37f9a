"""Unified WeightStore: one leaf API over the three QSQ weight forms.

A model parameter can live in three interchangeable representations:

* **dense**  — a plain array (``DenseWeight`` or a raw ``jax.Array``),
* **qsq**    — signed QSQ levels + per-group scalars (``QSQWeight``, the
  transport/checkpoint form: human-readable int8 levels),
* **packed** — 3-bit bit-planes + per-group scalars (``PackedWeight``, the
  HBM/serving form the Pallas fused dequant-matmul consumes directly).

Every leaf exposes the same surface — ``as_dense()``, ``matmul(x)``,
``nbits()`` — and is a registered pytree node, so whole param trees mix
representations freely, flow through ``jax.lax.scan`` (stacked layer axes
are sliced off the array children; the aux metadata is stack-invariant),
and jit/pjit like any array tree.

Grouping geometry: ``rest_ndim`` counts the trailing output dims after the
grouped (contraction) axis.  The number of leading stack axes is derived
from the arrays at use time (``ndim - 1 - rest_ndim``), so a leaf sliced by
a layer scan decodes itself correctly without metadata rewrites.

Tree-level helpers quantize a param pytree under a :class:`QuantPolicy`
(grouping along the true contraction axis when descriptors are supplied),
convert to/from the 3-bit wire format, and build serving trees that keep
kernel-eligible weights packed end-to-end.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.core.policy import QuantPolicy, path_str
from repro.core.qsq import (
    LEVEL_TABLE,
    SM_LEVEL_TABLE,
    QSQTensor,
    _quantize_impl,
    codes_to_levels,
    levels_to_codes,
    levels_to_smcodes,
    quantize,
    smcodes_to_levels,
)

# Logical axes a 2-D-view matmul contracts over, and path fragments that
# must never be served packed (gathered embeddings, routers, convs, norms,
# SSM decay params; attention wo contracts over heads x head_dim jointly and
# is excluded by the stack-prefix rule below).
CONTRACT_AXES = ("embed", "mlp", "heads_inner")
STACK_AXES = ("layers", None)
EXCLUDE_PATHS = ("tok", "router", "conv", "norm", "a_log", "dt_bias")


def _is_desc(x) -> bool:
    # duck-typed ParamDesc check (avoids importing repro.models here, which
    # would create an import cycle models.layers -> quant.store -> models)
    return hasattr(x, "axes") and hasattr(x, "shape") and hasattr(x, "dtype")


def contract_idx(desc) -> int | None:
    """Index of the first contraction axis in a ParamDesc, else None."""
    for i, name in enumerate(desc.axes):
        if name in CONTRACT_AXES:
            return i
    return None


def kernel_eligible(path: str, desc) -> bool:
    """True if this param can be served as bit-planes through qsq_matmul:
    the contraction axis is leading (after scan-stack axes only) and its
    length is a multiple of the 32-code plane word."""
    if any(e in path for e in EXCLUDE_PATHS):
        return False
    idx = contract_idx(desc)
    if idx is None:
        return False
    if any(a not in STACK_AXES for a in desc.axes[:idx]):
        return False
    return desc.shape[idx] % codec.PLANE_GROUP == 0


def _conv_view(leaf):
    """(kh, kw, cin, cout) -> channel-major view (cin, kh*kw*cout) (Fig. 5)."""
    w = jnp.moveaxis(leaf, 2, 0)
    return w.reshape(w.shape[0], -1)


def _conv_unview(levels_like, conv_shape):
    kh, kw, cin, cout = conv_shape
    return jnp.moveaxis(levels_like.reshape(cin, kh, kw, cout), 0, 2)


# --------------------------------------------------------------------------
# LSB plane truncation — the progressive-wire analogue of the paper's CSD
# LSB truncation: a lower quality tier is realized from an already-quantized
# artifact by zeroing the least-significant code bit-planes, never by
# re-quantizing.
# --------------------------------------------------------------------------
def _trunc_code_mask(drop: int) -> int:
    """3-bit code mask with the ``drop`` least-significant planes zeroed."""
    if not 0 <= drop < 3:
        raise ValueError(f"drop must be 0, 1 or 2; got {drop}")
    return (~((1 << drop) - 1)) & 0x7


def plane_mask_for_drop(drop: int) -> int:
    """Public alias of the tier code mask: ``drop`` LSB planes -> 3-bit mask.

    These are the per-row mask values :meth:`PackedWeight.matmul` accepts
    (0b111 / 0b110 / 0b100 for drop 0 / 1 / 2 — ``kernels.ref.MASK_VARIANTS``).
    """
    return _trunc_code_mask(drop)


def max_level_delta(drop: int) -> int:
    """Worst-case |level change| from dropping ``drop`` LSB code planes.

    The per-weight reconstruction error of a truncated tier is bounded by
    ``max_level_delta(drop) * alpha`` for each group's scalar alpha (0 for
    drop=0, 2 for drop=1, 4 for drop=2), for either code format.

    Under the sign-magnitude recode (wire v2, the packed serving format)
    the bit-2 sign plane survives every mask, so truncation degrades + and
    - levels identically: drop=1 maps +-1 -> 0 and +-4 -> +-2.  The legacy
    Table II offset layout (negatives are offset codes) truncates
    asymmetrically (+4 -> +2 but -4 exact at drop=1); the bound below is
    the max over both formats' valid codes, so it holds for legacy
    artifacts too.
    """
    mask = _trunc_code_mask(drop)
    sm_valid = (0, 1, 2, 3, 5, 6, 7)  # 4 (-0) unused on valid streams
    return int(max(
        max(abs(int(LEVEL_TABLE[c]) - int(LEVEL_TABLE[c & mask]))
            for c in range(7)),  # 7 itself is unused on valid streams
        max(abs(int(SM_LEVEL_TABLE[c]) - int(SM_LEVEL_TABLE[c & mask]))
            for c in sm_valid),
    ))


# --------------------------------------------------------------------------
# Leaf representations
# --------------------------------------------------------------------------
class WeightStore:
    """Uniform API over the dense / qsq / packed leaf representations."""

    kind: str = "?"

    def as_dense(self, dtype=jnp.float32) -> jax.Array:
        raise NotImplementedError

    def matmul(self, x: jax.Array) -> jax.Array:
        """x (..., K) contracted with this weight (K, *rest) -> (..., *rest)."""
        raise NotImplementedError

    def nbits(self) -> int:
        """Total stored bits of this representation."""
        raise NotImplementedError


def is_store(x) -> bool:
    return isinstance(x, WeightStore)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseWeight(WeightStore):
    """A dense array behind the WeightStore API."""

    value: jax.Array
    kind = "dense"

    def tree_flatten(self):
        return (self.value,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(value=children[0])

    @property
    def shape(self):
        return self.value.shape

    def as_dense(self, dtype=jnp.float32):
        return self.value.astype(dtype)

    def matmul(self, x):
        return jnp.tensordot(x, self.value.astype(x.dtype), axes=1)

    def nbits(self) -> int:
        return int(8 * self.value.size * jnp.dtype(self.value.dtype).itemsize)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QSQWeight(QSQTensor, WeightStore):
    """QSQ levels + scales, grouping axis anywhere (not just axis 0).

    Extends :class:`QSQTensor` (so legacy isinstance checks keep working)
    with ``rest_ndim``: the number of trailing dims after the grouped axis.
    ``None`` means legacy axis-0 grouping (``levels.ndim - 1``).  Leading
    stack axes (scan-stacked layers) are whatever remains; they are derived
    from the array rank at call time, which makes scan slicing transparent.
    """

    rest_ndim: int | None = None
    kind = "qsq"

    def tree_flatten(self):
        return (self.levels, self.scales), (
            self.group_size, self.phi, self.conv_shape, self.rest_ndim,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        levels, scales = children
        return cls(levels=levels, scales=scales, group_size=aux[0],
                   phi=aux[1], conv_shape=aux[2], rest_ndim=aux[3])

    @classmethod
    def from_tensor(cls, q: QSQTensor, rest_ndim: int | None = None):
        return cls(levels=q.levels, scales=q.scales, group_size=q.group_size,
                   phi=q.phi, conv_shape=q.conv_shape, rest_ndim=rest_ndim)

    def _rest(self) -> int:
        return self.rest_ndim if self.rest_ndim is not None else self.levels.ndim - 1

    def _stack(self) -> int:
        return self.levels.ndim - 1 - self._rest()

    def as_dense(self, dtype=jnp.float32):
        def dq(lev, sc):
            ng = sc.shape[0]
            g = lev.shape[0] // max(ng, 1)
            out = lev.astype(jnp.float32).reshape(ng, g, *lev.shape[1:]) * sc[:, None]
            return out.reshape(lev.shape)

        fn = dq
        for _ in range(self._stack()):
            fn = jax.vmap(fn)
        w = fn(self.levels, self.scales)
        if self.conv_shape is not None:
            w = _conv_unview(w, self.conv_shape)
        return w.astype(dtype)

    # override QSQTensor.dequantize (axis-0 only) with the rank-aware decode
    def dequantize(self, dtype=jnp.float32):
        return self.as_dense(dtype)

    def matmul(self, x):
        return jnp.tensordot(x, self.as_dense(x.dtype), axes=1)

    def truncate(self, drop: int) -> "QSQWeight":
        """Level-space LSB plane truncation (see :func:`max_level_delta`).

        Maps each level through its sign-magnitude code (wire v2) with the
        ``drop`` lowest code bits zeroed — bit-identical to
        ``pack().truncate(drop)`` but applicable to any grouping (conv
        views included).  The sign plane survives every mask, so + and -
        levels degrade alike.  Scales are kept; no re-quantization happens.
        """
        if drop == 0:
            return self
        mask = _trunc_code_mask(drop)
        levels = smcodes_to_levels(levels_to_smcodes(self.levels) & mask)
        return dataclasses.replace(self, levels=levels)

    def pack(self, sign_mag: bool = True) -> "PackedWeight":
        """-> bit-plane form.  The grouped axis length must be 32-aligned.

        Planes carry sign-magnitude codes by default (wire v2: symmetric
        truncation); pass ``sign_mag=False`` for the legacy Table II
        planes."""
        if self.conv_shape is not None:
            raise ValueError("conv-view QSQ weights are not kernel-servable")
        to_codes = levels_to_smcodes if sign_mag else levels_to_codes

        def enc(lev):
            return codec.pack_bitplane(to_codes(lev))

        fn = enc
        for _ in range(self._stack()):
            fn = jax.vmap(fn)
        return PackedWeight(planes=fn(self.levels), scales=self.scales,
                            group_size=self.group_size, phi=self.phi,
                            rest_ndim=self._rest(), sign_mag=sign_mag)

    # nbits() inherited from QSQTensor (same accounting for any grouping).


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedWeight(WeightStore):
    """Bit-plane packed 3-bit codes + per-group scalars — the serving form.

    planes: (*stack, K//32, 3, *rest) int32, scales: (*stack, K//G, *rest)
    f32.  ``matmul`` routes through the shape-aware kernel dispatcher
    (``kernels/dispatch.py``): the GEMV kernel at decode shapes, the tiled
    GEMM otherwise (interpret mode off-TPU), with ragged shapes zero-padded
    to the fitted tile — dense weights never materialize in HBM; decode
    happens in VREGs next to the MXU, per the paper's Table II
    shift-and-scale decoder.

    ``n_planes`` counts the *significant* planes (3 = full quality).  A
    quality-tier truncation (:meth:`truncate`) zeroes the dropped LSB plane
    words in place of removing them — the physical 3-slot layout is what the
    fused kernel consumes — and ``nbits()`` accounts only the kept planes,
    which is what an edge receiver of the truncated wire would store.

    ``tier_drops`` (optional, static aux) is the leaf's per-quality-tier
    plane-drop vector — entry t = LSB planes a request at tier index t
    drops from THIS weight.  It powers per-request quality: the planes stay
    at full quality and :meth:`matmul` takes a per-row ``plane_mask``
    operand instead (``tier_plane_masks()[tiers]``), so one mixed-tier
    batch serves every row at its own tier with no param-tree swap and no
    retrace.  Being aux (not data), it is stack-invariant under layer
    scans, exactly like the grouping metadata.

    ``sign_mag`` marks planes carrying sign-magnitude codes (wire v2);
    default False keeps directly-constructed Table II planes decoding as
    before.  ``plane_major`` marks the demand-streaming layout
    (*stack, 3, K//32, *rest), plane axis outermost after the stack and
    MSB first — the planes a truncated tier keeps are a leading prefix, so
    the fused kernel's HBM read shortens with demand
    (:meth:`to_plane_major`).
    """

    planes: jax.Array
    scales: jax.Array
    group_size: int
    phi: int
    rest_ndim: int = 0
    n_planes: int = 3
    tier_drops: tuple[int, ...] | None = None
    sign_mag: bool = False
    plane_major: bool = False
    kind = "packed"

    def tree_flatten(self):
        return (self.planes, self.scales), (
            self.group_size, self.phi, self.rest_ndim, self.n_planes,
            self.tier_drops, self.sign_mag, self.plane_major,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        planes, scales = children
        return cls(planes=planes, scales=scales, group_size=aux[0], phi=aux[1],
                   rest_ndim=aux[2], n_planes=aux[3] if len(aux) > 3 else 3,
                   tier_drops=aux[4] if len(aux) > 4 else None,
                   sign_mag=bool(aux[5]) if len(aux) > 5 else False,
                   plane_major=bool(aux[6]) if len(aux) > 6 else False)

    def _stack(self) -> int:
        return self.planes.ndim - 2 - self.rest_ndim

    @property
    def shape(self):
        """Logical dense shape."""
        st = self._stack()
        k_axis = st + 1 if self.plane_major else st
        k = self.planes.shape[k_axis] * codec.PLANE_GROUP
        return self.planes.shape[:st] + (k,) + self.planes.shape[st + 2:]

    def to_plane_major(self) -> "PackedWeight":
        """-> the demand-streaming layout: plane axis before K//32, MSB
        first, so a dropped trailing plane shortens the kernel's HBM read
        (instead of being masked after the load).  Lossless; idempotent."""
        if self.plane_major:
            return self
        st = self._stack()
        pm = jnp.flip(jnp.moveaxis(self.planes, st + 1, st), axis=st)
        return dataclasses.replace(self, planes=pm, plane_major=True)

    def to_interleaved(self) -> "PackedWeight":
        """Inverse of :meth:`to_plane_major` (the legacy layout)."""
        if not self.plane_major:
            return self
        st = self._stack()
        il = jnp.moveaxis(jnp.flip(self.planes, axis=st), st, st + 1)
        return dataclasses.replace(self, planes=il, plane_major=False)

    def truncate(self, drop: int) -> "PackedWeight":
        """Plane-truncated view: zero the ``drop`` LSB bit-planes.

        ``drop`` counts from full quality, so the call is idempotent and
        re-resolving a tier never deepens an earlier truncation by accident.
        The view's ``as_dense``/``matmul``/``nbits`` all reflect the
        truncation; the error vs the full-quality weight is bounded by
        ``max_level_delta(drop) * alpha`` per group.  On a plane-major leaf
        the zeroed planes are the trailing ones, which the demand-routed
        kernel then never reads at all.
        """
        if drop == 0:
            return self
        if not 0 < drop < 3:
            raise ValueError(f"drop must be 0, 1 or 2; got {drop}")
        st = self._stack()
        if self.plane_major:
            idx = (slice(None),) * st + (slice(3 - drop, 3),)
        else:
            idx = (slice(None),) * (st + 1) + (slice(0, drop),)
        return dataclasses.replace(
            self, planes=self.planes.at[idx].set(0),
            n_planes=min(self.n_planes, 3 - drop),
        )

    def unpack(self) -> QSQWeight:
        to_levels = smcodes_to_levels if self.sign_mag else codes_to_levels
        if self.plane_major:
            def dec(pl_):
                return to_levels(codec.unpack_bitplane_major(pl_))
        else:
            def dec(pl_):
                return to_levels(codec.unpack_bitplane(pl_))

        fn = dec
        for _ in range(self._stack()):
            fn = jax.vmap(fn)
        return QSQWeight(levels=fn(self.planes), scales=self.scales,
                         group_size=self.group_size, phi=self.phi,
                         rest_ndim=self.rest_ndim)

    def as_dense(self, dtype=jnp.float32):
        return self.unpack().as_dense(dtype)

    def tier_plane_masks(self) -> jax.Array | None:
        """Per-tier 3-bit code masks from ``tier_drops`` (None when the leaf
        has no tier vector or no tier ever drops a plane from it).  Index
        with a per-slot tier array to get the per-row ``plane_mask``
        operand :meth:`matmul` takes."""
        if not self.tier_drops or not any(self.tier_drops):
            return None
        return jnp.asarray(
            [_trunc_code_mask(d) for d in self.tier_drops], jnp.int32
        )

    def demand_drop(self, demand_tier: int | None = None) -> int:
        """Static plane-drop floor for a batch whose minimum live tier index
        is ``demand_tier``: every live row at tier >= demand_tier drops at
        least ``min(tier_drops[demand_tier:])`` planes from this leaf, so
        the kernel can skip that many trailing planes outright.  Physical
        truncation (``n_planes < 3``) widens the floor on plane-major
        leaves, where skipping actually shortens the HBM read."""
        drop = 0
        if demand_tier is not None and self.tier_drops:
            drop = min(self._live_tier_drops(demand_tier))
        if self.plane_major:
            drop = max(drop, 3 - self.n_planes)
        return int(drop)

    def _live_tier_drops(self, demand_tier: int | None) -> tuple[int, ...]:
        """Drops of the tiers at or above ``demand_tier`` (every tier when
        it is None) on a leaf with a tier vector."""
        if demand_tier is None:
            return self.tier_drops
        t = min(max(int(demand_tier), 0), len(self.tier_drops) - 1)
        return self.tier_drops[t:]

    def mask_variants(self, demand_tier: int | None = None) -> tuple[int, ...]:
        """Static mask variants a masked matmul at ``demand_tier`` unrolls:
        of ``MASK_VARIANTS[demand_drop:]``, in that order, the masks that
        some tier at or above the floor selects on this leaf.  A plan
        that drops at most one plane never selects ``0b100``, so its
        masked kernels decode at most two variants.  Without a tier
        vector, the whole suffix."""
        from repro.kernels.ref import MASK_VARIANTS  # deferred, as below

        suffix = MASK_VARIANTS[self.demand_drop(demand_tier):]
        if not self.tier_drops:
            return suffix
        live = {_trunc_code_mask(d)
                for d in self._live_tier_drops(demand_tier)}
        return tuple(v for v in suffix if v in live)

    def matmul(self, x, plane_mask: jax.Array | None = None,
               demand_tier: int | None = None):
        """Contract x (..., K) with this weight; optionally quality-tiered
        PER ROW.

        ``plane_mask`` holds one 3-bit code mask per leading-batch row of x
        (shape broadcastable over x's remaining lead dims, e.g. (B,) for a
        (B, S, K) x): row b's output is bit-identical to
        ``self.truncate(drop_b).matmul(x[b])`` — the tier dial as a masked
        term of the kernel's unpack, not a param swap.

        ``demand_tier`` (static python int) is the batch's minimum live
        tier index; combined with ``tier_drops`` it bounds how many
        trailing planes no row wants (:meth:`demand_drop`), and on
        plane-major leaves the kernel then streams only the demanded
        planes from HBM.  The kernel unrolls only :meth:`mask_variants`:
        every row's ``plane_mask`` must be one of them — a row with any
        other mask reads as zeros."""
        if self._stack():
            raise ValueError(
                "matmul on a stacked PackedWeight — slice the stack axis "
                "(e.g. via the layer scan) first"
            )
        rest = self.planes.shape[2:]
        k_words = self.planes.shape[1 if self.plane_major else 0]
        k = k_words * codec.PLANE_GROUP
        if x.shape[-1] != k:
            raise ValueError(f"x last dim {x.shape[-1]} != K {k}")
        n = int(np.prod(rest)) if rest else 1
        ng = self.scales.shape[0]
        g = k // ng
        lead = x.shape[:-1]
        m = int(np.prod(lead)) if lead else 1
        if plane_mask is not None:
            pm = jnp.asarray(plane_mask, jnp.int32)
            if pm.ndim > len(lead) or pm.shape != lead[: pm.ndim]:
                raise ValueError(
                    f"plane_mask shape {pm.shape} is not a leading prefix "
                    f"of x lead dims {lead}"
                )
            pm = pm.reshape(pm.shape + (1,) * (len(lead) - pm.ndim))
            plane_mask = jnp.broadcast_to(pm, lead if lead else (1,)).reshape(m)

        # Shape-aware kernel routing (kernels/dispatch.py): GEMV kernel at
        # decode shapes, tiled GEMM otherwise, zero-padded tiles for ragged
        # shapes, and the packed-representation XLA ref when the kernel
        # switch is off.  The dense weight is never materialized.
        from repro.kernels import dispatch  # deferred: pallas off cold paths

        pshape = (3, k_words, n) if self.plane_major else (k_words, 3, n)
        out = dispatch.packed_matmul(
            x.reshape(m, k),
            self.planes.reshape(pshape),
            self.scales.reshape(ng, n),
            group_size=g, use_kernel=_PACKED_MATMUL_KERNEL,
            plane_mask=plane_mask,
            sign_mag=self.sign_mag, plane_major=self.plane_major,
            demand_drop=self.demand_drop(demand_tier),
            variants=self.mask_variants(demand_tier),
        )
        return out.astype(x.dtype).reshape(*lead, *rest)

    def nbits(self) -> int:
        kept_plane_words = (self.planes.size // 3) * self.n_planes
        return int(32 * (kept_plane_words + self.scales.size))


# The kernel routing switch: benchmarks/tests flip this to compare the fused
# kernel against the XLA dequant+matmul on identical PackedWeight trees.
_PACKED_MATMUL_KERNEL = True


def set_packed_matmul_kernel(enabled: bool) -> None:
    global _PACKED_MATMUL_KERNEL
    _PACKED_MATMUL_KERNEL = bool(enabled)


# --------------------------------------------------------------------------
# Tree-level: quantize under a policy (contraction-aware when descs given)
# --------------------------------------------------------------------------
def quantize_tree(params, policy: QuantPolicy, descs=None):
    """Quantize selected leaves of a param pytree -> QSQWeight leaves.

    With ``descs`` (the model's ParamDesc tree), kernel-eligible matmul
    weights are grouped along their true contraction axis — vmapped over
    leading scan-stack axes — which is the layout both the wire format and
    the serving kernel want.  Other selected leaves (and everything when
    ``descs`` is None) keep the legacy axis-0 grouping; 4-D conv kernels are
    grouped in the channel-major view (paper Fig. 5).
    """

    def _eligible_leaf(path, leaf, desc):
        idx = contract_idx(desc)
        cfg = policy.config_for(path, leaf.shape[idx:])
        if cfg is None:
            return leaf

        def enc(w):
            return _quantize_impl(
                w, phi=cfg.phi, group_size=cfg.group_size, assign=cfg.assign,
                delta=cfg.delta, gamma_frac=cfg.gamma_frac,
                refit_alpha=cfg.refit_alpha,
            )

        fn = enc
        for _ in range(idx):
            fn = jax.vmap(fn)
        levels, scales = fn(leaf)
        return QSQWeight(levels=levels, scales=scales,
                         group_size=cfg.group_size, phi=cfg.phi,
                         rest_ndim=leaf.ndim - idx - 1)

    def _legacy_leaf(path, leaf):
        view = _conv_view(leaf) if leaf.ndim == 4 else leaf
        cfg = policy.config_for(path, view.shape)
        if cfg is None:
            return leaf
        q = quantize(view, cfg)
        if leaf.ndim == 4:
            q = dataclasses.replace(q, conv_shape=tuple(leaf.shape))
        return QSQWeight.from_tensor(q, rest_ndim=q.levels.ndim - 1)

    if descs is None:
        return jax.tree_util.tree_map_with_path(
            lambda p, a: _legacy_leaf(path_str(p), a), params
        )

    def _leaf(path, leaf, desc):
        p = path_str(path)
        if _is_desc(desc) and kernel_eligible(p, desc):
            return _eligible_leaf(p, leaf, desc)
        return _legacy_leaf(p, leaf)

    return jax.tree_util.tree_map_with_path(_leaf, params, descs)


def dense_tree(tree, like=None):
    """Decode every WeightStore/QSQTensor leaf to dense (others untouched).

    ``like`` (optional matching pytree of arrays/ShapeDtypeStructs) supplies
    target dtypes; defaults to f32.  Plain :class:`QSQTensor` leaves (from
    direct ``core.qsq.quantize`` calls) decode with their legacy axis-0
    grouping, conv view included.
    """

    def _decodable(x):
        return is_store(x) or isinstance(x, QSQTensor)

    def _leaf(leaf, ref=None):
        dtype = ref.dtype if ref is not None else jnp.float32
        if is_store(leaf):
            return leaf.as_dense(dtype)
        if isinstance(leaf, QSQTensor):
            w = leaf.dequantize(dtype)
            if leaf.conv_shape is not None:
                w = _conv_unview(w, leaf.conv_shape)
            return w
        return leaf

    if like is None:
        return jax.tree_util.tree_map(_leaf, tree, is_leaf=_decodable)
    return jax.tree_util.tree_map(_leaf, tree, like, is_leaf=_decodable)


def packable_leaf(path: str, leaf, desc) -> bool:
    """True if this QSQ leaf can be served as bit-planes through the fused
    kernel: kernel-eligible per its descriptor AND wire-grouped along the
    contraction axis with a 32-aligned length (legacy axis-0 wires fall back
    to dense decode)."""
    return (
        isinstance(leaf, QSQWeight)
        and leaf.conv_shape is None
        and _is_desc(desc)
        and kernel_eligible(path, desc)
        and leaf._rest() == len(desc.shape) - contract_idx(desc) - 1
        and leaf.levels.shape[contract_idx(desc)] % codec.PLANE_GROUP == 0
    )


def serve_tree(tree, descs, dtype=None, drop_map=None, tier_drop_map=None):
    """Serving layout: pack kernel-eligible QSQ leaves, decode the rest.

    This is what a quality-tiered engine holds: matmul weights stay in
    3-bit bit-plane form end-to-end (decoded tile-by-tile inside the fused
    kernel), while gathered/sensitive leaves (embeddings, norms, wo, convs)
    are decoded once at load.  ``drop_map`` (path -> LSB planes to drop)
    applies a quality-tier truncation to the packed leaves it names —
    realized on the already-quantized codes, never by re-quantizing.
    ``tier_drop_map`` (path -> per-tier drop vector) instead KEEPS the
    planes at full quality and stamps the vector on the packed leaf as
    ``tier_drops``, enabling per-request tier masking at matmul time
    (see :meth:`PackedWeight.matmul`); leaves it does not name serve full
    quality at every tier.  Returns (params_tree, n_packed).
    """
    n_packed = 0
    drop_map = drop_map or {}
    tier_drop_map = tier_drop_map or {}

    def _leaf(path, leaf, desc):
        nonlocal n_packed
        if not is_store(leaf):
            return leaf
        p = path_str(path)
        if packable_leaf(p, leaf, desc):
            n_packed += 1
            # sign-magnitude planes in the plane-major layout: truncation is
            # symmetric in sign, and dropped/undemanded trailing planes
            # shorten the kernel's HBM read instead of being masked.
            pw = leaf.pack().truncate(drop_map.get(p, 0)).to_plane_major()
            if p in tier_drop_map:
                pw = dataclasses.replace(
                    pw, tier_drops=tuple(int(d) for d in tier_drop_map[p])
                )
            return pw
        want = dtype if dtype is not None else getattr(desc, "dtype", jnp.float32)
        if p in drop_map:
            leaf = leaf.truncate(drop_map[p]) if isinstance(leaf, QSQWeight) else leaf
        return leaf.as_dense(want)

    out = jax.tree_util.tree_map_with_path(
        _leaf, tree, descs, is_leaf=lambda x: is_store(x)
    )
    return out, n_packed


def truncate_tree(tree, drop_map: dict):
    """Apply per-path LSB plane truncation to QSQ/packed leaves of a tree.

    ``drop_map`` maps '/'-joined pytree paths to planes-to-drop (from full
    quality).  Leaves not named, and leaves with no truncatable form, pass
    through untouched.
    """

    def _leaf(path, leaf):
        drop = drop_map.get(path_str(path), 0)
        if drop and isinstance(leaf, (QSQWeight, PackedWeight)):
            return leaf.truncate(drop)
        return leaf

    return jax.tree_util.tree_map_with_path(_leaf, tree, is_leaf=is_store)


def tree_bits_report(tree) -> dict:
    """Eq. 11/12 accounting over a mixed-representation tree."""
    total_bits = 0
    dense_bits = 0
    n_store = 0
    n_total = 0
    for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_store):
        n_total += 1
        if is_store(leaf):
            n_store += 1
            total_bits += leaf.nbits()
            dense_bits += int(8 * 4 * np.prod(leaf.shape))  # vs f32
        else:
            b = int(8 * leaf.size * jnp.dtype(leaf.dtype).itemsize)
            total_bits += b
            dense_bits += b
    return {
        "bits": total_bits,
        "dense_bits": dense_bits,
        "savings": 1.0 - total_bits / max(dense_bits, 1),
        "n_store_leaves": n_store,
        "n_leaves": n_total,
    }


# --------------------------------------------------------------------------
# Wire form: QSQWeight <-> {packed int32 words, scales, meta} dict.
# One codec for checkpoint export, DCN transfer and the serving load path.
# --------------------------------------------------------------------------
WIRE_FLAG = "__qsq__"

# Wire code formats: 1 = Table II offset codes (legacy, implied when the
# key is absent), 2 = sign-magnitude codes (symmetric plane truncation).
WIRE_CODE_FMT = 2


def is_wire_leaf(x) -> bool:
    return isinstance(x, dict) and bool(x.get(WIRE_FLAG, False))


def wire_encode_leaf(q: QSQTensor) -> dict:
    """Any QSQTensor/QSQWeight -> the dense-packed 3-bit wire dict.

    Wire v2: codes are sign-magnitude (``code_fmt: 2``), so an edge
    receiver can truncate LSB planes off the stream with + and - levels
    degrading alike.  :func:`wire_decode_leaf` still reads legacy v1
    (Table II) dicts, which carry no ``code_fmt`` key."""
    codes = levels_to_smcodes(q.levels).reshape(-1)
    rest = q.rest_ndim if isinstance(q, QSQWeight) and q.rest_ndim is not None \
        else q.levels.ndim - 1
    return {
        WIRE_FLAG: True,
        "packed": codec.pack_dense(codes, bits=3),
        "scales": q.scales,
        "shape": tuple(int(s) for s in q.levels.shape),
        "group_size": int(q.group_size),
        "phi": int(q.phi),
        "rest_ndim": int(rest),
        "conv_shape": tuple(int(s) for s in q.conv_shape) if q.conv_shape else (),
        "code_fmt": WIRE_CODE_FMT,
    }


def wire_decode_leaf(d: dict) -> QSQWeight:
    """Inverse of :func:`wire_encode_leaf` (lossless: codes + scales exact).

    Tolerates legacy wire dicts (no rest_ndim => axis-0 grouping; no
    code_fmt => Table II offset codes) and npz-roundtripped metadata
    (numpy scalars/arrays instead of ints/tuples).
    """
    shape = tuple(int(s) for s in np.asarray(d["shape"]).reshape(-1))
    n = int(np.prod(shape)) if shape else 1
    codes = codec.unpack_dense(jnp.asarray(d["packed"]), n).reshape(shape)
    conv = tuple(int(s) for s in np.asarray(d.get("conv_shape", ())).reshape(-1))
    rest = d.get("rest_ndim", None)
    fmt_raw = d.get("code_fmt", None)
    fmt = int(np.asarray(fmt_raw)) if fmt_raw is not None else 1
    if fmt not in (1, WIRE_CODE_FMT):
        raise ValueError(f"unknown wire code_fmt {fmt}")
    to_levels = smcodes_to_levels if fmt == WIRE_CODE_FMT else codes_to_levels
    return QSQWeight(
        levels=to_levels(codes),
        scales=jnp.asarray(d["scales"]),
        group_size=int(d["group_size"]),
        phi=int(d["phi"]),
        conv_shape=conv if conv else None,
        rest_ndim=int(np.asarray(rest)) if rest is not None else None,
    )


def tree_to_wire(tree) -> Any:
    """Store tree -> wire tree (raw leaves pass through untouched)."""

    def _leaf(leaf):
        if isinstance(leaf, PackedWeight):
            return wire_encode_leaf(leaf.unpack())
        if isinstance(leaf, QSQTensor):
            return wire_encode_leaf(leaf)
        if isinstance(leaf, DenseWeight):
            return leaf.value
        return leaf

    return jax.tree_util.tree_map(
        _leaf, tree, is_leaf=lambda x: is_store(x) or isinstance(x, QSQTensor)
    )


def tree_from_wire(wire) -> Any:
    """Wire tree -> store tree with QSQWeight leaves."""
    return jax.tree_util.tree_map(
        lambda x: wire_decode_leaf(x) if is_wire_leaf(x) else x,
        wire, is_leaf=is_wire_leaf,
    )
