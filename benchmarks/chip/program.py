"""Builds the system under test for a configuration: the weights from the
seed, ``repro.api.compress`` and the per-request packed engine, warmed up
on the shapes the cell's traffic uses."""
from __future__ import annotations

import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.chip import cells, weights, work


def arch_config(config: dict, here: Path = cells.HERE):
    """The program's ArchConfig for ``config``, from the adapter of the
    reference it names (``programs/<reference>.py``)."""
    return cells.adapter(config, here).arch_config(config)


def check_layout(model, specs: dict) -> None:
    """The program's parameter tree has the leaves the reference draws."""
    from repro.core.policy import path_str
    from repro.models.base import is_desc

    got = {path_str(p): (tuple(d.shape), np.dtype(d.dtype).name)
           for p, d in jax.tree_util.tree_flatten_with_path(
               model.param_descs(), is_leaf=is_desc)[0]}
    want = {p: (tuple(s.shape), s.dtype) for p, s in specs.items()}
    if got != want:
        raise ValueError(f"the program's parameter layout differs from the "
                         f"reference's: {sorted(set(got.items()) ^ set(want.items()))}")


def build(config: dict, ref, seed: int, log=lambda msg: None,
          here: Path = cells.HERE):
    """The per-request packed engine serving ``config`` with weights from
    ``seed``, its served tree on the default device; the program's
    architecture comes from the adapter under ``here``.

    The weights are drawn on the default device; compression runs on the
    host's CPU device, the offline step of the edge flow, where its f32
    arithmetic is exact (so the lossless weights rank every leaf alike and
    the tiers resolve in path order) and where packing the 102400 x 4096
    embedding does not meet the TPU's tiled layout (a single packed leaf
    there needs more than the chip's 16 GB).  The served tree is then put
    on the device the engine runs on."""
    from repro import api
    from repro.models.api import Model

    t = time.perf_counter()
    model = Model(arch_config(config, here))
    specs = ref.leaf_specs(config, config["quant"]["group"])
    check_layout(model, specs)
    params = jax.block_until_ready(weights.draw_tree(seed, specs))
    log(f"weights drawn: {time.perf_counter() - t:.1f} s")
    device = jax.devices()[0]
    host = jax.devices("cpu")[0]
    t = time.perf_counter()
    params = jax.device_put(params, host)
    serve = config["serve"]
    tiers = config["quant"]["tiers"]
    with jax.default_device(host):
        art = api.compress(model, params)
        del params
        log(f"compressed on the host: {time.perf_counter() - t:.1f} s")
        t = time.perf_counter()
        eng = art.engine(quality=tiers[0], batch_slots=serve["batch_slots"],
                         max_len=serve["max_len"],
                         max_prompt=serve["max_prompt"])
    eng.params = jax.block_until_ready(jax.device_put(eng.params, device))
    log(f"engine built, served tree on {device.platform}: "
        f"{time.perf_counter() - t:.1f} s")
    if not eng.per_request_quality or eng.tier_names != tiers:
        raise ValueError(f"the engine does not serve the tiers {tiers} per "
                         f"request (it has {eng.tier_names})")
    return eng


def tier_plan_faults(eng, quant: dict) -> list[str]:
    """Leaves whose per-tier plane drops in the engine's artifact differ
    from the drops the configuration states, which the reference serves.
    On lossless weights every leaf's quantization error is 0, so the
    artifact's ranking, and with it the mid tier, is the path order the
    configuration writes down; a ranking that rounding has reordered
    serves another mid tier than the reference computes."""
    zero = (0,) * len(quant["tiers"])
    want = work.tier_vectors(quant["drops"], quant["tiers"], quant["packed"])
    got = eng.artifact.tier_drop_vectors()
    return [f"{p}: program {tuple(got.get(p, zero))}, configuration "
            f"{tuple(want.get(p, zero))}"
            for p in sorted(set(want) | set(got))
            if tuple(got.get(p, zero)) != tuple(want.get(p, zero))]


def warm(eng, traffic: dict, vocab: int) -> None:
    """Compile what the cell's traffic will run, then leave an empty stream
    open.  For each tier the traffic uses: the admission program at that
    tier's demand floor on a fresh stream's cache and on a live one (the
    two differ to the compile cache) and the decode program at that floor,
    which is the floor of any batch whose best tier it is."""
    rng = np.random.default_rng(0)
    for tier in sorted(t for t, w in traffic["tiers"].items() if w > 0):
        eng.reset_stream()
        for _ in range(2):
            eng.submit(rng.integers(0, vocab, 8).tolist(), 3, quality=tier)
        eng.run_until_drained()
    eng.reset_stream()
    eng.advance_clock(0.0)  # opens the new stream's session now
    jax.block_until_ready(eng.params)
