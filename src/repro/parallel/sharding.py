"""Logical-axis sharding: models annotate tensors with logical names
("batch", "seq", "heads", ...) and this module maps them onto the physical
mesh, with automatic divisibility fallback.

Why auto-drop: jit *argument* shardings must divide the dimension exactly
(GSPMD only pads intermediates). Several assigned configs have awkward dims
— InternVL2's vocab is 92,553 (odd), long_500k has batch=1, GQA kv_heads=8
never divide TP=16. ``spec_for`` drops mesh axes that do not divide, so a
single rule set serves every (arch x shape x mesh) cell.

Models call :func:`shard` which is a no-op outside a :func:`sharding_ctx`
— smoke tests and kernels run un-annotated on one device.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisRule = Union[None, str, Tuple[str, ...]]

# Default logical-axis -> mesh-axis rules (single pod). launch/mesh.py
# extends "batch" with the "pod" axis for the multi-pod mesh.
DEFAULT_RULES: Dict[str, AxisRule] = {
    "batch": ("data",),
    "seq": None,
    "kvseq": ("model",),       # SP decode: KV-cache sequence over TP axis
    "heads": ("model",),
    "embed": None,
    "ffn": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "fsdp": ("data",),         # ZeRO-3 param axis
    "tp": ("model",),          # tensor-parallel param axis
    "layers": None,
    "state": None,
}


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[Dict[str, AxisRule]] = None


_CTX = _Ctx()


@contextmanager
def sharding_ctx(mesh: Mesh, rules: Optional[Dict[str, AxisRule]] = None):
    prev = (_CTX.mesh, _CTX.rules)
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    _CTX.mesh, _CTX.rules = mesh, merged
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _axis_size(mesh: Mesh, axes: AxisRule) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def spec_for(shape: Sequence[int], logical: Sequence[Optional[str]],
             mesh: Mesh, rules: Dict[str, AxisRule],
             allow_uneven: bool = False) -> P:
    """Build a PartitionSpec, dropping axes that don't divide the dim."""
    entries = []
    used: set = set()                     # a mesh axis may appear only once
    for dim, name in zip(shape, logical):
        rule = rules.get(name) if name else None
        if rule is None:
            entries.append(None)
            continue
        axes = (rule,) if isinstance(rule, str) else tuple(rule)
        # keep the largest prefix of unused mesh axes that divides this dim
        kept = []
        prod = 1
        for a in axes:
            if a not in mesh.shape or a in used:
                continue
            nxt = prod * mesh.shape[a]
            if allow_uneven or dim % nxt == 0:
                kept.append(a)
                prod = nxt
        used.update(kept)
        entries.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*entries)


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Constrain an intermediate to its logical sharding (no-op w/o ctx)."""
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None or not hasattr(x, "shape") or x.ndim != len(logical):
        return x
    spec = spec_for(x.shape, logical, mesh, rules, allow_uneven=True)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named(mesh: Mesh, rules: Dict[str, AxisRule], shape: Sequence[int],
          *logical: Optional[str]) -> NamedSharding:
    """Argument-grade sharding (strict divisibility)."""
    return NamedSharding(mesh, spec_for(shape, logical, mesh, rules))


def batch_sharding(mesh: Mesh, shape: Sequence[int],
                   rules: Optional[Dict[str, AxisRule]] = None
                   ) -> NamedSharding:
    """Argument sharding for a batch-leading tensor via the "batch" rule.

    The serving engine's data-parallel entry point: a padded request
    super-batch ``(replicas * micro_batch, ...)`` device_put with this
    sharding lands one replica's micro-batch on each shard of the mesh
    "data" axis. Non-batch dims stay unsharded; a batch the data axis
    does not divide falls back to replicated (``spec_for`` auto-drop).
    """
    logical = ("batch",) + (None,) * (len(shape) - 1)
    return named(mesh, rules or DEFAULT_RULES, shape, *logical)


def data_parallel(f, mesh: Mesh, axis: str = "data"):
    """``f(params, x)`` run once per shard of mesh ``axis``: params
    replicated, ``x`` and the result split on their leading (batch) dim.

    XLA cannot partition a compiled Pallas kernel (a ``tpu_custom_call``)
    by itself, so a batch-sharded forward is an explicit ``shard_map``:
    each device runs the kernels on its own slice of the batch.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=(P(), P(axis)),
                         out_specs=P(axis), check_vma=False)


# ---------------------------------------------------------------------------
# Parameter shardings: leaf-name -> logical axes per dimension
# ---------------------------------------------------------------------------

# Matched against the *last* dict key of the tree path. A leading "layers"
# axis (scan-stacked blocks) is detected by rank mismatch and left unsharded.
_PARAM_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    # embeddings / head
    "embed": ("vocab", "fsdp"),
    "lm_head": ("fsdp", "vocab"),
    "frontend_proj": ("fsdp", "tp"),
    # attention (flat head dims)
    "wq": ("fsdp", "tp"),
    "wk": ("fsdp", "tp"),
    "wv": ("fsdp", "tp"),
    "wo": ("tp", "fsdp"),
    # dense mlp
    "wi": ("fsdp", "tp"),
    "wdown": ("tp", "fsdp"),
    # MoE
    "router": (None, None),
    "moe_wi": ("experts", "fsdp", "tp"),
    "moe_wdown": ("experts", "tp", "fsdp"),
    # mamba2
    "in_proj": ("fsdp", "tp"),
    "out_proj": ("tp", "fsdp"),
    "conv_w": (None, "tp"),
    # xlstm
    "wqkv": ("fsdp", "tp"),
    "w_up": ("fsdp", "tp"),
    "w_gates": ("fsdp", "tp"),
    "r_gates": (None, "tp"),
}


def param_spec(path, leaf) -> Tuple[Optional[str], ...]:
    """Logical axes for one parameter leaf."""
    key = None
    for entry in reversed(path):
        name = getattr(entry, "key", None)
        if isinstance(name, str):
            key = name
            break
    axes = _PARAM_AXES.get(key)
    if axes is None:
        return (None,) * leaf.ndim               # norms, biases, scalars
    if leaf.ndim == len(axes) + 1:               # scan-stacked: leading L axis
        return ("layers",) + axes
    if leaf.ndim != len(axes):
        return (None,) * leaf.ndim
    return axes


def param_shardings(mesh: Mesh, rules: Dict[str, AxisRule], params):
    """NamedSharding pytree for a param (or shape) pytree."""
    def one(path, leaf):
        return named(mesh, rules, leaf.shape, *param_spec(path, leaf))
    return jax.tree_util.tree_map_with_path(one, params)
