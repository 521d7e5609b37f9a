"""Compile the packed-matmul kernels for a TPU v5e that is described, not
attached.

Interpret mode accepts any tile, so only the TPU compiler can refuse a
block that breaks the (8, 128) tiling rule or overflows VMEM.  Each case
plans its tiles with ``dispatch.plan(..., backend="tpu")`` exactly as the
served path does on the chip, lowers the Pallas kernel with
``interpret=False`` on abstract shapes, and checks that the compiled
program holds the kernel (``tpu_custom_call``) under the stable name its
``pallas_call`` gives it, the name the chip benchmark's trace reduction
matches.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import dispatch, ops, ref

G = 16  # artifact.default_policy group size


def _smollm_matmuls():
    """(name, K, N) of every packed SmolLM-135M matmul at full width."""
    c = get_arch("smollm_135m")
    q, kv = c.n_heads * c.hd, c.n_kv * c.hd
    return [("wq", c.d_model, q), ("wk_wv", c.d_model, kv),
            ("wg_wu", c.d_model, c.d_ff), ("wd", c.d_ff, c.d_model),
            ("head", c.d_model, c.vocab)]


# decode (4 slots), speculative verify (4 slots x k+1 = 5), admission
# prefill (ServeConfig.max_prompt)
SERVE_M = {"decode": 4, "verify": 20, "prefill": 64}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means the chip cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_chip(sharding, m, k, n, *, masked, demand_drop=0,
                      variants=None):
    p = dispatch.plan(m, k, n, G, backend="tpu")
    n_variants = len(ref.mask_variants(demand_drop, variants))

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    planes = s((3, k // 32, p.pn), jnp.int32)
    scales = s((k // G, p.pn), jnp.float32)
    # the served layout: plane-major sign-magnitude planes
    kw = dict(group_size=G, bk=p.bk, bn=p.bn, interpret=False,
              demand_drop=demand_drop, sign_mag=True, plane_major=True)
    gemv = p.route == dispatch.ROUTE_GEMV
    if not gemv:
        kw["bm"] = p.bm
    if masked:
        kw["variants"] = variants
        x = s((n_variants, p.pm, k), jnp.bfloat16)
        fn = ops.qsq_matvec_masked if gemv else ops.qsq_matmul_masked
    else:
        x = s((p.pm, k), jnp.bfloat16)
        fn = ops.qsq_matvec if gemv else ops.qsq_matmul
    compiled = jax.jit(lambda a, b, c: fn(a, b, c, **kw)).lower(
        x, planes, scales).compile()
    want = ("qsq_matvec" if gemv else "qsq_matmul") + ("_masked" if masked else "")
    assert _kernel_names(compiled.as_text()) == [want], (m, k, n, p)
    return p


def _kernel_names(hlo: str) -> list[str]:
    """The HLO instruction names of the program's Pallas kernels, without
    their numeric suffix: what the chip benchmark's trace reduction
    matches (``%qsq_matvec_masked.43 = ... custom-call(...)``)."""
    return sorted({m.group(1) for m in re.finditer(
        r"%([\w.-]+?)\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)})


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
@pytest.mark.parametrize("phase", list(SERVE_M))
@pytest.mark.parametrize("name,k,n", _smollm_matmuls(),
                         ids=[t[0] for t in _smollm_matmuls()])
def test_smollm_matmul_compiles_for_v5e(one_chip, name, k, n, phase, masked):
    m = SERVE_M[phase]
    p = _compile_for_chip(one_chip, m, k, n, masked=masked)
    want = dispatch.ROUTE_GEMV if m <= dispatch.GEMV_M_MAX else dispatch.ROUTE_GEMM
    assert p.route == want


def test_deepseek_wd_gemv_compiles_for_v5e(one_chip):
    # deepseek-7b wd: the largest divisor of K = 11008 under the preferred
    # tile, 1376, is not lane-aligned; the tile must drop to 256
    p = _compile_for_chip(one_chip, 8, 11008, 4096, masked=False)
    assert p.route == dispatch.ROUTE_GEMV


def _deepseek_matmuls():
    """(name, K, N) of the packed DeepSeek-7B matmuls at full width."""
    c = get_arch("deepseek_7b")
    return [("wq", c.d_model, c.n_heads * c.hd), ("wg_wu", c.d_model, c.d_ff),
            ("wd", c.d_ff, c.d_model), ("head", c.d_model, c.vocab)]


# the served variant sets of a plan that drops at most one plane: hi live
# (two variants, all planes stream), or mid/lo alone (one, two planes)
SERVED_VARIANTS = {"2var": (0, (0b111, 0b110)), "1var": (1, (0b110,))}


@pytest.mark.parametrize("variants", list(SERVED_VARIANTS))
@pytest.mark.parametrize("m", [16, 64], ids=["decode", "prefill"])
@pytest.mark.parametrize("name,k,n", _deepseek_matmuls(),
                         ids=[t[0] for t in _deepseek_matmuls()])
def test_deepseek_masked_compiles_for_v5e(one_chip, name, k, n, m, variants):
    # 16 decode slots take the GEMV, a 64-token admission the GEMM; both
    # keep their HLO names whatever the variant set
    demand_drop, vs = SERVED_VARIANTS[variants]
    p = _compile_for_chip(one_chip, m, k, n, masked=True,
                          demand_drop=demand_drop, variants=vs)
    want = dispatch.ROUTE_GEMV if m <= dispatch.GEMV_M_MAX else dispatch.ROUTE_GEMM
    assert p.route == want


@pytest.mark.parametrize("m", [4, 64])
def test_demand_drop_compiles_for_v5e(one_chip, m):
    # lo-tier demand: only the sign plane streams
    _compile_for_chip(one_chip, m, 576, 1536, masked=True, demand_drop=2)


def test_quantize_kernel_name_for_v5e(one_chip):
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda a: ops.qsq_quantize(
        a, group_size=G, interpret=False)).lower(w).compile()
    assert _kernel_names(compiled.as_text()) == ["qsq_quantize"]
