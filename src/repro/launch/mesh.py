"""Production mesh + logical-axis sharding rules.

Single pod:  (16, 16)     axes ("data", "model")   — 256 chips
Multi pod:   (2, 16, 16)  axes ("pod", "data", "model") — 512 chips

`make_production_mesh` is a FUNCTION so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

from typing import Mapping

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices the test process has."""
    return _mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Mesh axes that carry the batch (pod is an outer DP axis)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def sharding_rules(mesh, *, fsdp: bool = True) -> Mapping[str, tuple]:
    """Logical axis name -> mesh axes.

    * model-parallel dims (heads / mlp / vocab / experts) -> "model"
    * FSDP: the residual "embed" dim of weight matrices shards over "data"
      (+"pod" when present), zero-3 style — params are gathered per layer
      inside the scan.  Disable for small models that fit replicated.
    * batch -> ("pod", "data"); decode kv-cache seq -> "model" (long-context
      caches are the dominant decode-state and shard over the model axis).
    """
    dp = data_axes(mesh)
    rules = {
        "batch": dp,
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "mlp": ("model",),
        "experts": ("model",),
        "heads_inner": ("model",),  # mamba d_inner / ssm heads
        "seq_kv": ("model",),  # decode caches: shard the sequence dim
        "seq_act": (),  # context parallelism (activations' seq dim) — opt-in
        "embed": dp if fsdp else (),
        "layers": (),
    }
    return rules


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))
