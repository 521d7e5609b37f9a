"""Per-row plane masks: the kernel-level contract of per-request quality.

The tentpole invariant: ``PackedWeight.matmul(x, plane_mask=m)`` computes
row b EXACTLY as ``truncate(drop_b).matmul(x)[b]`` would — a dropped plane
is a masked term of the in-kernel unpack, so a quality tier is a per-row
mask flip, not a param-tree swap.  Checked bit-for-bit across the GEMV,
GEMM and XLA-ref dispatch routes, padded (ragged) shapes included, and the
per-weight truncation error stays within the documented
``max_level_delta(drop) * alpha`` bound.

Property tests run under hypothesis when it is installed; on a clean
interpreter they fall back to a fixed seed sweep of the same checks.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - depends on the environment
    HAS_HYPOTHESIS = False

from repro.core.qsq import QSQConfig, quantize
from repro.kernels import dispatch
from repro.kernels.ref import MASK_VARIANTS
from repro.quant.store import (
    QSQWeight,
    max_level_delta,
    plane_mask_for_drop,
    set_packed_matmul_kernel,
)


def _packed(k, n, g, seed, sign_mag=True):
    rng = np.random.RandomState(seed)
    w = jnp.asarray(rng.randn(k, n), jnp.float32)
    q = QSQWeight.from_tensor(
        quantize(w, QSQConfig(group_size=g, refit_alpha=True)), rest_ndim=1
    )
    return q.pack(sign_mag=sign_mag)


def _check_masked_rows_match_truncated(m, kmul, n, g, seed, use_kernel):
    """Each masked-matmul row is bit-identical to the whole-weight
    truncation at that row's drop, on the route the dispatcher picks."""
    k = 32 * kmul
    if k % g:
        g = 32
    pw = _packed(k, n, g, seed)
    rng = np.random.RandomState(seed + 1)
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    drops = rng.randint(0, 3, size=m)
    masks = jnp.asarray([plane_mask_for_drop(int(d)) for d in drops], jnp.int32)
    set_packed_matmul_kernel(use_kernel)
    try:
        got = np.asarray(pw.matmul(x, plane_mask=masks))
        for d in (0, 1, 2):
            rows = np.where(drops == d)[0]
            if len(rows) == 0:
                continue
            want = np.asarray(pw.truncate(int(d)).matmul(x))
            np.testing.assert_array_equal(got[rows], want[rows])
    finally:
        set_packed_matmul_kernel(True)


def _check_truncation_error_bound(kmul, n, g, seed):
    """|truncate(drop) - full| <= max_level_delta(drop) * alpha, per group."""
    k = 32 * kmul
    if k % g:
        g = 32
    pw = _packed(k, n, g, seed)
    full = np.asarray(pw.as_dense())
    scales = np.asarray(pw.scales)
    for drop in (1, 2):
        err = np.abs(np.asarray(pw.truncate(drop).as_dense()) - full)
        err_g = err.reshape(scales.shape[0], pw.group_size, -1)
        bound = max_level_delta(drop) * np.abs(scales[:, None, :]) + 1e-6
        assert np.all(err_g <= bound), (drop, float((err_g - bound).max()))


if HAS_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        m=st.integers(1, 24),
        kmul=st.integers(1, 4),
        n=st.integers(8, 200),
        g=st.sampled_from([16, 32, 64]),
        seed=st.integers(0, 2**16),
        use_kernel=st.booleans(),
    )
    def test_masked_rows_match_truncated(m, kmul, n, g, seed, use_kernel):
        _check_masked_rows_match_truncated(m, kmul, n, g, seed, use_kernel)

    @settings(max_examples=10, deadline=None)
    @given(
        kmul=st.integers(1, 4),
        n=st.integers(8, 128),
        g=st.sampled_from([16, 32, 64]),
        seed=st.integers(0, 2**16),
    )
    def test_truncation_error_bound(kmul, n, g, seed):
        _check_truncation_error_bound(kmul, n, g, seed)

else:  # pragma: no cover - fallback sweep on hypothesis-less interpreters

    @pytest.mark.parametrize("m,kmul,n,g,seed,use_kernel", [
        (1, 1, 8, 16, 0, True),
        (4, 2, 48, 16, 1, True),
        (3, 4, 100, 32, 2, True),
        (24, 3, 130, 64, 3, True),
        (8, 2, 64, 16, 4, False),
        (17, 1, 200, 32, 5, False),
    ])
    def test_masked_rows_match_truncated(m, kmul, n, g, seed, use_kernel):
        _check_masked_rows_match_truncated(m, kmul, n, g, seed, use_kernel)

    @pytest.mark.parametrize("kmul,n,g,seed", [
        (1, 8, 16, 0), (2, 48, 32, 1), (4, 128, 64, 2),
    ])
    def test_truncation_error_bound(kmul, n, g, seed):
        _check_truncation_error_bound(kmul, n, g, seed)


# --------------------------------------------------------------------------
# Variant sets: a leaf's tier plan picks the masks its kernels unroll
# --------------------------------------------------------------------------
# drops per tier (hi first): DEFAULT_TIERS on a leaf that mid drops, a plan
# that drops two planes, and a non-monotone vector
TIER_PLANS = {"default": (0, 1, 1), "two_planes": (0, 1, 2),
              "nonmonotone": (1, 2, 0, 2)}


def _record_variant_splits(monkeypatch):
    """Record the leading dim of every variant-split xs that reaches a
    masked kernel or the masked reference."""
    from repro.kernels import ops, ref

    seen = []
    for mod, name in ((ops, "qsq_matvec_masked"), (ops, "qsq_matmul_masked"),
                      (ref, "qsq_matmul_masked_ref")):
        def spy(xs, *a, _fn=getattr(mod, name), **kw):
            seen.append(xs.shape[0])
            return _fn(xs, *a, **kw)
        monkeypatch.setattr(mod, name, spy)
    return seen


@pytest.mark.parametrize("sign_mag", [True, False], ids=["sign_mag", "table2"])
@pytest.mark.parametrize("m,route", [(4, "gemv"), (40, "gemm"), (4, "xla")])
@pytest.mark.parametrize("plan", list(TIER_PLANS))
def test_variant_set_rows_match_truncated(plan, m, route, sign_mag,
                                          monkeypatch):
    """At every demand floor the call unrolls exactly the leaf's variant
    set (an ordered subset of the demand suffix, ``0b100`` only where a
    tier drops two planes); each row equals the truncated weight's row,
    and a row whose mask is outside the set reads exactly 0."""
    tier_drops = TIER_PLANS[plan]
    pw = dataclasses.replace(_packed(64, 48, 16, 11, sign_mag=sign_mag),
                             tier_drops=tier_drops).to_plane_major()
    rng = np.random.RandomState(12)
    x = jnp.asarray(rng.randn(m, 64), jnp.float32)
    table = np.asarray(pw.tier_plane_masks())
    seen = _record_variant_splits(monkeypatch)
    set_packed_matmul_kernel(route != "xla")
    try:
        want = {d: np.asarray(pw.truncate(d).matmul(x)) for d in (0, 1, 2)}
        for demand in range(len(tier_drops)):
            variants = pw.mask_variants(demand)
            suffix = MASK_VARIANTS[pw.demand_drop(demand):]
            assert variants == tuple(v for v in suffix if v in variants)
            assert (0b100 in variants) == (2 in tier_drops[demand:])
            tiers = rng.randint(demand, len(tier_drops), size=m)
            masks = table[tiers]
            outside = [v for v in MASK_VARIANTS if v not in variants]
            if outside:
                masks[0] = outside[0]
            seen.clear()
            got = np.asarray(pw.matmul(x, plane_mask=jnp.asarray(masks),
                                       demand_tier=demand))
            assert seen == [len(variants)]
            for row, drop in enumerate(np.asarray(tier_drops)[tiers]):
                if row == 0 and outside:
                    np.testing.assert_array_equal(got[0], 0)
                else:
                    np.testing.assert_array_equal(got[row], want[drop][row])
    finally:
        set_packed_matmul_kernel(True)


# --------------------------------------------------------------------------
# Fixed-case contracts (not property-swept)
# --------------------------------------------------------------------------
def test_mask_variants_cover_all_drops():
    assert tuple(plane_mask_for_drop(d) for d in (0, 1, 2)) == MASK_VARIANTS


def test_masked_call_counts_and_routes_like_unmasked():
    """The masked operand must not change the dispatch plan — same route,
    same tiling, one extra ':masked' counter."""
    pw = _packed(64, 48, 16, 0)
    x = jnp.ones((4, 64), jnp.float32)
    masks = jnp.full((4,), plane_mask_for_drop(1), jnp.int32)
    dispatch.reset_counters()
    pw.matmul(x)
    unmasked = dict(dispatch.counters)
    dispatch.reset_counters()
    pw.matmul(x, plane_mask=masks)
    masked = dict(dispatch.counters)
    route = dispatch.plan(4, 64, 48, 16).route
    assert unmasked[route] == 1 and masked[route] == 1
    assert masked[f"{route}:masked"] == 1
    dispatch.reset_counters()


def test_plane_mask_broadcasts_over_seq_dim():
    """(B,) masks on a (B, S, K) x apply per slot across the sequence —
    the prefill case."""
    pw = _packed(64, 48, 16, 7)
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(2, 5, 64), jnp.float32)
    masks = jnp.asarray([plane_mask_for_drop(0), plane_mask_for_drop(2)],
                        jnp.int32)
    got = np.asarray(pw.matmul(x, plane_mask=masks))
    np.testing.assert_array_equal(got[0], np.asarray(pw.matmul(x[0])))
    np.testing.assert_array_equal(
        got[1], np.asarray(pw.truncate(2).matmul(x[1])))


def test_plane_mask_bad_shape_raises():
    pw = _packed(64, 48, 16, 8)
    x = jnp.ones((4, 64), jnp.float32)
    with pytest.raises(ValueError, match="plane_mask"):
        pw.matmul(x, plane_mask=jnp.zeros((3,), jnp.int32))


def test_plane_mask_for_drop_validates():
    with pytest.raises(ValueError, match="drop"):
        plane_mask_for_drop(3)
