"""Demand-driven plane streaming: layout, routing, wire v2 and traffic.

The tentpole contract under test —

* the plane-major layout is a lossless, invertible re-view of the packed
  planes, and plane truncation on it zeroes a TRAILING prefix-complement,
  so the demand-routed kernel can shorten the HBM read instead of masking
  post-load;
* ``matmul(x, plane_mask, demand_tier=t)`` is bit-identical to the PR 5
  masked path (``demand_tier=None``) for every tier mix whose live rows
  all sit at tier >= t, across the GEMV / GEMM / XLA dispatch routes;
* sign-magnitude (wire v2) codes make plane truncation sign-symmetric,
  and the wire codec round-trips v2 while still reading legacy Table II
  dicts;
* the dispatch ``traffic`` counter reports planes-touched x tiles and
  plane words read/full per routed call;
* the continuous engine computes per-tick demand from live slots only,
  never retraces beyond one trace per tier, and its analytic stream
  meter shows an all-lo batch reading <= 0.5x the all-hi weight bytes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import ArchConfig
from repro.core.qsq import QSQConfig, quantize
from repro.kernels import dispatch
from repro.models.api import Model
from repro.models.base import init_params
from repro.quant.artifact import QualitySpec, QualityTier
from repro.quant.store import (
    QSQWeight,
    plane_mask_for_drop,
    set_packed_matmul_kernel,
    wire_decode_leaf,
    wire_encode_leaf,
)
from repro.serve.scheduler import plane_demand


def _packed(k, n, g, seed, tier_drops=None, plane_major=False):
    rng = np.random.RandomState(seed)
    w = jnp.asarray(rng.randn(k, n), jnp.float32)
    q = QSQWeight.from_tensor(
        quantize(w, QSQConfig(group_size=g, refit_alpha=True)), rest_ndim=1
    )
    pw = q.pack()
    if tier_drops is not None:
        pw = dataclasses.replace(pw, tier_drops=tuple(tier_drops))
    return pw.to_plane_major() if plane_major else pw


# --------------------------------------------------------------------------
# Layout: plane-major <-> interleaved
# --------------------------------------------------------------------------
def test_plane_major_roundtrip_lossless():
    pw = _packed(64, 48, 16, 0)
    pm = pw.to_plane_major()
    assert pm.plane_major and pm.to_plane_major() is pm  # idempotent
    back = pm.to_interleaved()
    np.testing.assert_array_equal(np.asarray(back.planes),
                                  np.asarray(pw.planes))
    np.testing.assert_array_equal(np.asarray(pm.as_dense()),
                                  np.asarray(pw.as_dense()))
    assert pm.shape == pw.shape and pm.nbits() == pw.nbits()


def test_plane_major_truncate_zeroes_trailing_planes():
    """LSB truncation on the MSB-first plane-major layout zeroes TRAILING
    plane slots — the kept planes are a leading prefix, which is what lets
    the kernel's BlockSpec stop reading early."""
    pw = _packed(96, 40, 32, 1)
    for drop in (1, 2):
        tr_pm = pw.to_plane_major().truncate(drop)
        np.testing.assert_array_equal(
            np.asarray(tr_pm.planes[3 - drop:]), 0)
        assert np.asarray(tr_pm.planes[:3 - drop]).any()
        # same dense view as truncating the interleaved layout
        np.testing.assert_array_equal(
            np.asarray(tr_pm.as_dense()),
            np.asarray(pw.truncate(drop).as_dense()))
        assert tr_pm.demand_drop() == drop  # physical floor, no tiers


def test_stacked_plane_major_keeps_layer_axis_leading():
    """The plane axis sits AFTER the stack axes, so layer-scan slicing of
    axis 0 still yields per-layer leaves on plane-major trees."""
    pw = _packed(64, 16, 16, 2)
    stacked = dataclasses.replace(
        pw, planes=jnp.stack([pw.planes, pw.planes]),
        scales=jnp.stack([pw.scales, pw.scales]))
    pm = stacked.to_plane_major()
    assert pm.planes.shape == (2, 3) + pw.planes.shape[0:1] + pw.planes.shape[2:]
    assert pm.shape == (2,) + pw.shape


# --------------------------------------------------------------------------
# Demand routing == the PR 5 masked path, every tier mix, every route
# --------------------------------------------------------------------------
@pytest.mark.parametrize("m,route", [(4, "gemv"), (64, "gemm"), (4, "xla")])
def test_demand_routed_bit_identical_to_masked(m, route):
    tier_drops = (0, 1, 2)
    pw = _packed(64, 48, 16, 3, tier_drops=tier_drops, plane_major=True)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(m, 64), jnp.float32)
    masks_tbl = pw.tier_plane_masks()
    set_packed_matmul_kernel(route != "xla")
    try:
        for demand in (0, 1, 2):
            # every mix of live tiers at or above the demand floor
            tiers = jnp.asarray(rng.randint(demand, 3, size=m), jnp.int32)
            baseline = np.asarray(pw.matmul(x, plane_mask=masks_tbl[tiers]))
            routed = np.asarray(pw.matmul(x, plane_mask=masks_tbl[tiers],
                                          demand_tier=demand))
            np.testing.assert_array_equal(routed, baseline, err_msg=(
                f"route={route} demand={demand}"))
    finally:
        set_packed_matmul_kernel(True)


def test_demand_prunes_stale_rows_to_zero():
    """A row whose mask demands a PRUNED variant (stale dead-lane tier
    below the floor) reads exact zeros — the engine discards dead-lane
    outputs, so zeros are safe, but they must be deterministic."""
    pw = _packed(64, 32, 16, 5, tier_drops=(0, 1, 2), plane_major=True)
    x = jnp.ones((4, 64), jnp.float32)
    masks = jnp.asarray([plane_mask_for_drop(0), plane_mask_for_drop(1),
                         plane_mask_for_drop(2), plane_mask_for_drop(1)],
                        jnp.int32)
    out = np.asarray(pw.matmul(x, plane_mask=masks, demand_tier=1))
    np.testing.assert_array_equal(out[0], 0)      # drop-0 row: pruned
    assert np.abs(out[1:]).sum() > 0              # demanded rows survive
    want = np.asarray(pw.matmul(x, plane_mask=masks))
    np.testing.assert_array_equal(out[1:], want[1:])


def test_demand_drop_suffix_min_handles_nonmonotone_tiers():
    pw = _packed(32, 8, 16, 6, tier_drops=(1, 2, 0, 2))
    # interleaved: demand never shortens (no physical prefix to skip)
    assert [pw.demand_drop(t) for t in (None, 0, 1, 2, 3)] == [0, 0, 0, 0, 2]
    pm = pw.to_plane_major()
    assert [pm.demand_drop(t) for t in (0, 1, 2, 3)] == [0, 0, 0, 2]
    assert pm.truncate(1).demand_drop(0) == 1  # physical floor widens


def test_unmasked_demand_requires_plane_major():
    from repro.kernels import ops

    pw = _packed(64, 32, 16, 7)
    x = jnp.ones((2, 64), jnp.float32)
    with pytest.raises(ValueError, match="plane-major"):
        ops.qsq_matvec(x, pw.planes.reshape(2, 3, 32), pw.scales,
                       group_size=16, demand_drop=1)


# --------------------------------------------------------------------------
# Sign-magnitude codes (wire v2)
# --------------------------------------------------------------------------
def test_sign_symmetric_truncation():
    """Wire v2's reason to exist: +v and -v degrade IDENTICALLY under
    plane truncation (Table II offset codes truncated +1 to 0 but -1 to
    -2, biasing truncated tiers negative)."""
    levels = jnp.asarray([[0, 1, 2, 4, -1, -2, -4, 1]], jnp.float32).T
    q = QSQWeight(levels=levels, scales=jnp.ones((1, 1)), group_size=8,
                  phi=4, rest_ndim=1)
    for drop in (1, 2):
        t = np.asarray(q.truncate(drop).levels)[:, 0]
        pos, neg = t[1:4], t[4:7]
        np.testing.assert_array_equal(pos, -neg)


def test_wire_v2_roundtrip_and_legacy_shim():
    from repro.core import codec
    from repro.core.qsq import levels_to_codes

    pw_src = _packed(64, 24, 16, 8)
    q = pw_src.unpack()
    d = wire_encode_leaf(q)
    assert int(np.asarray(d["code_fmt"])) == 2
    back = wire_decode_leaf(d)
    np.testing.assert_array_equal(np.asarray(back.levels),
                                  np.asarray(q.levels))
    # legacy v1 dict: Table II offset codes, no code_fmt key
    legacy = dict(d)
    del legacy["code_fmt"]
    legacy["packed"] = codec.pack_dense(
        levels_to_codes(jnp.asarray(q.levels)).reshape(-1), bits=3)
    old = wire_decode_leaf(legacy)
    np.testing.assert_array_equal(np.asarray(old.levels),
                                  np.asarray(q.levels))
    bad = dict(d, code_fmt=9)
    with pytest.raises(ValueError, match="code_fmt"):
        wire_decode_leaf(bad)


def test_pack_defaults_to_sign_magnitude():
    pw = _packed(64, 16, 16, 9)
    assert pw.sign_mag
    legacy = _packed(64, 16, 16, 9).unpack().pack(sign_mag=False)
    assert not legacy.sign_mag
    np.testing.assert_array_equal(np.asarray(pw.as_dense()),
                                  np.asarray(legacy.as_dense()))


# --------------------------------------------------------------------------
# Traffic accounting
# --------------------------------------------------------------------------
def test_traffic_counts_demand_shortened_reads():
    pw = _packed(64, 48, 16, 10, tier_drops=(0, 1, 2), plane_major=True)
    x = jnp.ones((4, 64), jnp.float32)
    masks = pw.tier_plane_masks()
    dispatch.reset_counters()
    pw.matmul(x, plane_mask=masks[jnp.zeros(4, jnp.int32)], demand_tier=0)
    full = dispatch.traffic["plane_words_read"]
    assert full == dispatch.traffic["plane_words_full"] > 0
    route = dispatch.plan(4, 64, 48, 16).route
    assert dispatch.traffic[f"{route}:planes3"] == 1
    dispatch.reset_counters()
    pw.matmul(x, plane_mask=masks[jnp.full(4, 2, jnp.int32)], demand_tier=2)
    assert dispatch.traffic["plane_words_read"] * 3 == full
    assert dispatch.traffic[f"{route}:planes1"] == 1
    assert dispatch.traffic["plane_reads"] > 0
    dispatch.reset_counters()
    # interleaved leaves can't shorten: always 3 planes streamed
    pw.to_interleaved().matmul(x, plane_mask=masks[jnp.full(4, 2, jnp.int32)],
                               demand_tier=2)
    assert (dispatch.traffic["plane_words_read"]
            == dispatch.traffic["plane_words_full"])
    dispatch.reset_counters()


def test_reset_counters_clears_traffic():
    dispatch.traffic["x"] = 1  # qsqlint: disable=QSQ005 -- seeds the reset test
    dispatch.counters["y"] = 1  # qsqlint: disable=QSQ005 -- seeds the reset test
    dispatch.reset_counters()
    assert not dispatch.traffic and not dispatch.counters


# --------------------------------------------------------------------------
# Scheduler demand + engine integration
# --------------------------------------------------------------------------
def test_plane_demand_is_min_live_tier():
    assert plane_demand([2, 0, 1]) == 0
    assert plane_demand([2, 2]) == 2
    assert plane_demand([], default=1) == 1
    assert plane_demand(iter(np.asarray([1, 2], np.int32))) == 1


STREAM_TIERS = QualitySpec((
    QualityTier("hi", drop_planes=0, drop_frac=0.0),
    QualityTier("mid", drop_planes=1, drop_frac=1.0),
    QualityTier("lo", drop_planes=2, drop_frac=1.0),
))


@pytest.fixture(scope="module")
def stream_artifact():
    cfg = ArchConfig(name="smollm-like", family="dense", n_layers=2,
                     d_model=64, n_heads=4, n_kv=2, d_ff=128, vocab=256,
                     dtype=jnp.float32, remat=False)
    model = Model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_descs())
    return api.compress(model, params, tiers=STREAM_TIERS)


def test_engine_demand_updates_without_retrace(stream_artifact, no_retrace):
    """Admissions and evictions move the per-tick demand; after one warm
    trace per tier neither program retraces again, whatever the mix."""
    art = stream_artifact
    eng = art.engine(quality="hi", batch_slots=2, max_prompt=6, max_len=16)
    for q in art.quality_names():  # warm one trace per demand pattern
        eng.submit([3, 1], max_new=2, quality=q)
        eng.run_until_drained()
    n_tiers = len(art.quality_names())
    assert eng._cont_step._cache_size() == n_tiers
    assert eng._admit._cache_size() == n_tiers
    # lo decoding alone (demand=lo), hi admitted mid-stream (demand drops
    # to hi), hi evicts first (demand returns to lo): three demand moves
    with no_retrace(eng._cont_step, eng._admit):
        r_lo = eng.submit([9, 9], max_new=8, quality="lo")
        eng.step()
        r_hi = eng.submit([5, 5], max_new=2, quality="hi")
        out = eng.run_until_drained()
    assert len(out[r_lo].tokens) == 8 and len(out[r_hi].tokens) == 2


def test_engine_masked_calls_unroll_only_selectable_variants(monkeypatch):
    """On DEFAULT_TIERS no tier drops two planes: every masked call unrolls
    at most two variants at floor hi and one at floor lo; at floor mid, one
    on the leaves mid already truncates.  The trace-time counters show the
    pruning against the demand suffix."""
    cfg = ArchConfig(name="smollm-like", family="dense", n_layers=2,
                     d_model=64, n_heads=4, n_kv=2, d_ff=128, vocab=256,
                     dtype=jnp.float32, remat=False)
    model = Model(cfg)
    art = api.compress(model, init_params(jax.random.PRNGKey(1),
                                          model.param_descs()))
    eng = art.engine(quality="hi", batch_slots=2, max_prompt=6, max_len=16)
    calls = []
    real = dispatch.packed_matmul

    def spy(*a, **kw):
        if kw.get("plane_mask") is not None:
            calls.append(len(kw["variants"]))
        return real(*a, **kw)

    monkeypatch.setattr(dispatch, "packed_matmul", spy)
    dispatch.reset_counters()
    by_floor = {}
    for q in art.quality_names():  # one fresh trace per demand floor
        calls.clear()
        eng.submit([3, 1], max_new=2, quality=q)
        eng.run_until_drained()
        by_floor[q] = list(calls)
    assert by_floor["hi"] and max(by_floor["hi"]) == 2
    assert set(by_floor["mid"]) == {1, 2} and set(by_floor["lo"]) == {1}
    leaves = [leaf for leaf in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: hasattr(x, "tier_drops"))
        if getattr(leaf, "tier_drops", None)]
    assert leaves and all(
        len(leaf.mask_variants(1)) == len(set(leaf.tier_drops[1:]))
        for leaf in leaves)
    t = dispatch.traffic
    assert t["mask_variants"] == sum(map(sum, by_floor.values()))
    assert 3 * t["mask_variants"] <= 2 * t["mask_variants_suffix"]
    dispatch.reset_counters()


def test_engine_stream_meter_all_lo_under_half_of_all_hi(stream_artifact):
    """ISSUE acceptance: all-lo bytes-read-per-token <= 0.5x all-hi
    (analytic meter; the tier ladder keeps one plane at lo, so the exact
    ratio is 1/3)."""
    art = stream_artifact
    eng = art.engine(quality="hi", batch_slots=2, max_prompt=6, max_len=16)
    prompts = [[1, 2], [7, 7, 7], [4], [9, 9]]

    def run_mix(quality):
        eng.reset_stream()
        for p in prompts:
            eng.submit(p, max_new=4, quality=quality)
        eng.run_until_drained()
        return eng.stream_stats()

    hi, lo = run_mix("hi"), run_mix("lo")
    assert hi["tokens"] == lo["tokens"] == len(prompts) * 4
    assert hi["read_frac"] == 1.0
    assert lo["bytes_per_token"] <= 0.5 * hi["bytes_per_token"]
    assert lo["read_frac"] == pytest.approx(1 / 3, abs=1e-6)
