"""Kernel dispatch: shape-keyed routing, tile padding, tuned-table IO.

Covers the ISSUE-3 acceptance criteria: ragged shapes go through PADDED
kernel dispatch (never the old dense-dequant materialization), config
selection is deterministic and table-overridable, and the global kernel
switch still forces the pure-XLA packed reference everywhere.
"""
import jax
import numpy as np
import pytest

from repro.core import codec
from repro.kernels import dispatch, ref
from repro.quant.store import PackedWeight, set_packed_matmul_kernel


@pytest.fixture(autouse=True)
def _clean_dispatch_state():
    dispatch.reset_counters()
    yield
    set_packed_matmul_kernel(True)
    dispatch.set_tuned_table(None)
    dispatch.reset_counters()


def _packed(k, n, g, seed=0):
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * 0.05
    codes, scales = ref.qsq_quantize_ref(w, g, 4)
    return codec.pack_bitplane(codes), scales


def _pw(k, n, g, seed=0):
    planes, scales = _packed(k, n, g, seed)
    return PackedWeight(planes=planes, scales=scales, group_size=g, phi=4,
                        rest_ndim=1)


# --------------------------------------------------------------------------
# Planning
# --------------------------------------------------------------------------
def test_plan_routes_by_shape_class():
    pv = dispatch.plan(8, 2048, 2048, 64)
    pm = dispatch.plan(128, 2048, 2048, 64)
    assert pv.route == dispatch.ROUTE_GEMV
    assert pm.route == dispatch.ROUTE_GEMM
    assert dispatch.plan(1, 4096, 4096, 64).route == dispatch.ROUTE_GEMV


def test_plan_is_deterministic():
    a = [dispatch.plan(m, 2080, 300, 16) for m in (1, 8, 64)]
    b = [dispatch.plan(m, 2080, 300, 16) for m in (1, 8, 64)]
    assert a == b


def test_plan_tiles_divide_padded_shape():
    for m, k, n, g in [(3, 2080, 300, 16), (8, 96, 17, 32), (100, 4096, 777, 64),
                       (8, 1024, 64, 128), (256, 160, 96, 32)]:
        p = dispatch.plan(m, k, n, g)
        assert p.pm % p.bm == 0 and p.pn % p.bn == 0 and p.k % p.bk == 0
        assert p.pm >= m and p.pn >= n
        assert p.bk % codec.PLANE_GROUP == 0 and p.bk % g == 0


def test_plan_never_pads_k():
    # K is always a common multiple of 32 and G, so an exact K tile exists
    for k, g in [(2080, 16), (96, 24), (4096, 64), (160, 32)]:
        p = dispatch.plan(8, k, 64, g)
        assert p.k % p.bk == 0


def _mosaic_legal_k_tile(p, g):
    """A K tile is the whole K, or lane-aligned with sublane-aligned plane
    (bk//32) and scale (bk//G) blocks — the TPU lowering's (8, 128) rule."""
    return p.bk == p.k or (p.k % p.bk == 0 and p.bk % dispatch.LANE == 0
                           and (p.bk // codec.PLANE_GROUP) % dispatch.SUBLANE == 0
                           and (p.bk // g) % dispatch.SUBLANE == 0)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("m", [4, 64])
@pytest.mark.parametrize("g", [16, 32, 64])
@pytest.mark.parametrize("k", [576, 1536, 4096, 11008, 17408])
def test_plan_k_tile_is_whole_or_lane_aligned(k, g, m, backend):
    p = dispatch.plan(m, k, 4096, g, backend=backend)
    assert _mosaic_legal_k_tile(p, g), p
    assert p.bk == p.k or p.bk % 128 == 0


def test_plan_keeps_tuned_tiles_mosaic_legal():
    """A tuned-table entry cannot produce an illegal block: ragged
    preferences are refitted to lane/sublane-aligned tiles."""
    dispatch.set_tuned_table({"tpu": {
        "gemv": {"kind": "gemv", "bm": 8, "bk": 288, "bn": 200},
        "gemm": {"kind": "gemm", "bm": 12, "bk": 1376, "bn": 300},
    }})
    for m, k, n in [(4, 1536, 576), (8, 11008, 4096), (64, 11008, 4096),
                    (64, 1536, 49152)]:
        p = dispatch.plan(m, k, n, 16, backend="tpu")
        assert _mosaic_legal_k_tile(p, 16), p
        assert p.bn == p.pn or p.bn % dispatch.LANE == 0, p
        assert p.bm == p.pm or p.bm % dispatch.SUBLANE == 0, p


def test_use_kernel_false_routes_to_xla_ref():
    assert dispatch.plan(8, 1024, 256, 64, use_kernel=False).route == \
        dispatch.ROUTE_XLA


def test_tuned_table_exact_key_overrides_class_default():
    backend = jax.default_backend()
    base = dispatch.plan(8, 1024, 256, 64)
    dispatch.set_tuned_table({backend: {
        dispatch.shape_key(8, 1024, 256, 64):
            {"kind": "gemv", "bm": 8, "bk": 512, "bn": 128},
    }})
    tuned = dispatch.plan(8, 1024, 256, 64)
    assert (tuned.bk, tuned.bn) == (512, 128)
    assert (tuned.bk, tuned.bn) != (base.bk, base.bn)
    # other shapes keep their class defaults
    assert dispatch.plan(8, 2048, 256, 64).bk == base.bk == \
        dispatch.plan(8, 1024, 512, 64).bk


def test_table_cannot_force_gemv_on_large_m():
    backend = jax.default_backend()
    dispatch.set_tuned_table({backend: {
        "gemm": {"kind": "gemv", "bm": 8, "bk": 1024, "bn": 256},
    }})
    assert dispatch.plan(512, 1024, 256, 64).route == dispatch.ROUTE_GEMM


def test_tuned_table_json_roundtrip(tmp_path):
    table = {
        "tpu": {
            "gemv": dispatch.TileConfig(kind="gemv", bm=8, bk=2048, bn=512),
            dispatch.shape_key(8, 4096, 4096, 64):
                {"kind": "gemv", "bm": 8, "bk": 1024, "bn": 256},
        },
        "cpu": {"gemm": {"kind": "gemm", "bm": 128, "bk": 256, "bn": 128}},
    }
    path = dispatch.save_tuned_table(table, tmp_path / "t.json")
    loaded = dispatch.load_tuned_table(path)
    assert loaded == {
        "tpu": {
            "gemv": {"kind": "gemv", "bm": 8, "bk": 2048, "bn": 512},
            "8x4096x4096g64": {"kind": "gemv", "bm": 8, "bk": 1024, "bn": 256},
        },
        "cpu": {"gemm": {"kind": "gemm", "bm": 128, "bk": 256, "bn": 128}},
    }
    # and the loaded table actually drives planning
    dispatch.set_tuned_table(loaded | {
        jax.default_backend(): loaded["tpu"],
    })
    assert dispatch.plan(8, 4096, 4096, 64).bk == 1024


def test_checked_in_table_is_valid():
    table = dispatch.load_tuned_table(dispatch.DEFAULT_TABLE_PATH)
    assert "tpu" in table and "cpu" in table
    for _backend, entries in table.items():
        for _key, cfg in entries.items():
            tc = dispatch.TileConfig(**cfg)
            assert tc.kind in ("gemv", "gemm")
            assert tc.bk % codec.PLANE_GROUP == 0


# --------------------------------------------------------------------------
# Execution: ragged shapes through padded kernels, never dense
# --------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n,g", [(8, 2080, 300, 16), (64, 2080, 300, 16)])
def test_ragged_shapes_pad_and_match_ref(m, k, n, g):
    """Acceptance: tile-ragged shapes (K=2080, N=300) go through padded
    kernel dispatch and match the XLA ref — the dense as_dense() path is
    gone (no route for it exists, and the trace counters prove which
    kernel ran)."""
    planes, scales = _packed(k, n, g)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    dispatch.reset_counters()
    out = dispatch.packed_matmul(x, planes, scales, group_size=g)
    want = ref.qsq_matmul_ref(x, planes, scales, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-4)
    route = dispatch.ROUTE_GEMV if m <= dispatch.GEMV_M_MAX else dispatch.ROUTE_GEMM
    assert dispatch.counters[route] == 1
    assert dispatch.counters[f"{route}:padded"] == 1
    assert dispatch.counters[dispatch.ROUTE_XLA] == 0


def test_packed_weight_ragged_matmul_never_dense():
    """PackedWeight.matmul on a ragged (K=2080, N=300) weight takes the
    padded kernel path (dispatch trace), not a dense materialization."""
    g = 16
    pw = _pw(2080, 300, g)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 2080))
    dispatch.reset_counters()
    out = pw.matmul(x)
    want = ref.qsq_matmul_ref(x, pw.planes, pw.scales, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-4)
    assert dispatch.counters[dispatch.ROUTE_GEMV] == 1
    assert dispatch.counters[f"{dispatch.ROUTE_GEMV}:padded"] == 1
    assert sum(dispatch.counters.values()) == 2  # route + route:padded only


def test_kernel_switch_forces_xla_ref_everywhere():
    """set_packed_matmul_kernel(False) must route EVERY packed matmul to
    the pure-XLA packed reference (still no dense-weight leaf path)."""
    g = 32
    pw = _pw(256, 96, g, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 256))
    set_packed_matmul_kernel(False)
    dispatch.reset_counters()
    out = pw.matmul(x)
    big = _pw(2080, 300, 16, seed=5)
    out2 = big.matmul(jax.random.normal(jax.random.PRNGKey(6), (128, 2080)))
    assert dispatch.counters[dispatch.ROUTE_XLA] == 2
    assert dispatch.counters[dispatch.ROUTE_GEMV] == 0
    assert dispatch.counters[dispatch.ROUTE_GEMM] == 0
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.qsq_matmul_ref(x, pw.planes, pw.scales, g)),
        rtol=2e-5, atol=2e-4)
    assert out2.shape == (128, 300)


def test_dispatch_counters_under_jit():
    """Routing happens at trace time, so jitted callers still record it."""
    g = 64
    pw = _pw(1024, 256, g, seed=7)
    x = jax.random.normal(jax.random.PRNGKey(8), (8, 1024))
    dispatch.reset_counters()
    out = jax.jit(pw.matmul)(x)
    assert dispatch.counters[dispatch.ROUTE_GEMV] == 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.qsq_matmul_ref(x, pw.planes, pw.scales, g)),
        rtol=2e-5, atol=2e-4)
