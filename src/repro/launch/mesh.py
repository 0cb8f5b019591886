"""Production mesh construction (function, not module-level constant — so
importing this module never touches jax device state)."""
from __future__ import annotations

from typing import Dict

import jax

from repro.parallel.sharding import AxisRule


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def rules_for_mesh(mesh, *, seq_shard_batch1: bool = False
                   ) -> Dict[str, AxisRule]:
    """Logical-axis rule overrides for a given mesh.

    multi-pod: the "pod" axis joins the batch (pure DP across pods).
    seq_shard_batch1 (long_500k): KV-cache sequence spreads over every axis
    (batch=1 cannot shard), giving full sequence parallelism for the cache.
    """
    rules: Dict[str, AxisRule] = {}
    axes = tuple(mesh.axis_names)
    if "pod" in axes:
        rules["batch"] = ("pod", "data")
        rules["fsdp"] = ("data",)          # params replicated across pods
    if seq_shard_batch1:
        rules["kvseq"] = tuple(a for a in ("data", "model") if a in axes)
    return rules


def smoke_mesh(n: int = 1):
    """Tiny mesh over however many devices exist (tests)."""
    dev = len(jax.devices())
    d = min(n, dev)
    return jax.make_mesh((d, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
