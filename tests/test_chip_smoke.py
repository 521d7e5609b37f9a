"""chip_smoke.run on the SmolLM-135M smoke config, on the CPU.

The chip run drives the same function at full width; here the Pallas
kernels run in interpret mode, so what this checks is the control flow and
the checks themselves: every request ends DONE, the packed prefill logits
match the script's own jax.numpy reference, and the dispatcher routed every
packed matmul to a Pallas kernel.
"""
import importlib.util
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.quant.store import QSQWeight


def _chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_run_on_smoke_config():
    smoke = _chip_smoke()
    r = smoke.run(get_arch("smollm_135m", smoke=True), n_requests=2,
                  max_new=4)
    # two plain requests (hi, mid) and the speculative hi one, all DONE
    assert r["requests"] == 3 and r["tokens_served"] == 3 * 4
    assert r["spec_drafted"] > 0
    assert r["logit_rel"] <= smoke.LOGIT_RTOL
    assert r["routes"]["xla_ref"] == 0
    assert r["routes"]["pallas_gemv"] > 0 and r["routes"]["pallas_gemm"] > 0


def test_chip_smoke_reference_dequantizes_like_the_store():
    """The script's own unpacking of plane-major sign-magnitude planes
    agrees with the weight store on a layer-stacked leaf with two output
    dims (the wq layout: (layers, K, heads, head_dim))."""
    smoke = _chip_smoke()
    rng = np.random.default_rng(0)
    k, g = 64, 16
    levels = rng.choice([0, 1, 2, 4, -1, -2, -4], size=(2, k, 3, 8))
    scales = rng.uniform(0.01, 0.1, size=(2, k // g, 3, 8))
    pw = QSQWeight(levels=jnp.asarray(levels, jnp.int8),
                   scales=jnp.asarray(scales, jnp.float32), group_size=g,
                   phi=4, rest_ndim=2).pack().to_plane_major()
    np.testing.assert_array_equal(np.asarray(smoke._dequantize(pw)),
                                  np.asarray(pw.as_dense()))
