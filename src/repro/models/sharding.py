"""Logical-axis sharding: MaxText-style rules mapping logical axis names to
mesh axes, with graceful no-op behaviour when no mesh is active (CPU smoke
tests) and divisibility-aware fallback (e.g. kv_heads=1 cannot shard 16-way).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

MeshAxes = Union[None, str, Tuple[str, ...]]

# Default logical→mesh rules. ``data``-like axes map to all data-parallel mesh
# axes; ``model``-like axes to the tensor-parallel axis. The optimized
# configuration adds sequence parallelism by mapping ``act_seq`` → model.
DEFAULT_RULES: Dict[str, MeshAxes] = {
    # parameter axes
    "vocab": "model",
    "embed": None,
    "mlp": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "experts": "model",
    "expert_mlp": None,
    "lru": "model",
    # SSD inner dims stay replicated: the fused in_proj mixes z/x/B/C/dt
    # channel groups, and mamba2-370m is small enough that pure DP is the
    # realistic deployment (see DESIGN §Arch-applicability).
    "ssm_inner": None,
    "ssm_state": None,
    "conv": None,
    "layers": None,           # stacked-scan leading axis, never sharded
    # optimizer state extra sharding (ZeRO-1): applied in train/optimizer
    "zero": "data",
    # activation axes
    "act_batch": ("pod", "data"),
    "act_seq": None,          # → "model" when sequence parallelism enabled
    "act_kv_seq": None,       # KV-cache seq axis; → "data" for long-context
    "act_embed": None,
    "act_heads": "model",
    "act_mlp": "model",
    "act_vocab": "model",
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Dict[str, MeshAxes] = dict(DEFAULT_RULES)


_CTX = _Ctx()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[Dict[str, MeshAxes]] = None):
    """Activate a mesh + logical rules for model construction/lowering."""
    prev_mesh, prev_rules = _CTX.mesh, _CTX.rules
    _CTX.mesh = mesh
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    _CTX.rules = merged
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev_mesh, prev_rules


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def resolve_spec(logical_axes: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None,
                 mesh: Optional[Mesh] = None) -> PS:
    """Map logical axis names to a PartitionSpec under the active rules.

    If ``shape`` is given, drops sharding on any dim not divisible by its mesh
    axis size (e.g. kv_heads=4 over a 16-way model axis → replicated).
    """
    mesh = mesh or _CTX.mesh
    parts = []
    used: set = set()
    for i, name in enumerate(logical_axes):
        axes = _CTX.rules.get(name) if name else None
        if axes is not None and mesh is not None:
            present = tuple(a for a in ((axes,) if isinstance(axes, str) else axes)
                            if a in mesh.shape and a not in used)
            axes = present if present else None
            if axes is not None and shape is not None:
                if shape[i] % _axis_size(mesh, axes) != 0:
                    axes = None
            if axes is not None:
                used.update(axes)
        elif mesh is None:
            axes = None
        if axes is None:
            parts.append(None)
        elif isinstance(axes, tuple) and len(axes) == 1:
            parts.append(axes[0])
        else:
            parts.append(axes)
    while parts and parts[-1] is None:
        parts.pop()
    return PS(*parts)


def _manual_axes() -> set:
    """Mesh axes currently in Manual (shard_map) mode — constraints must not
    mention them (e.g. the compressed-gradient pod-manual region)."""
    am = jax.sharding.get_abstract_mesh()
    return {n for n, t in zip(am.axis_names, am.axis_types)
            if t == jax.sharding.AxisType.Manual}


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = resolve_spec(logical_axes, shape=x.shape, mesh=mesh)
    manual = _manual_axes()
    if manual:
        parts = []
        for p in spec:
            if p is None:
                parts.append(None)
                continue
            ax = tuple(a for a in ((p,) if isinstance(p, str) else p)
                       if a not in manual)
            parts.append(ax[0] if len(ax) == 1 else (ax or None))
        spec = PS(*parts)
        # the constraint must carry the abstract mesh, whose axis types mark
        # the manual axes
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(jax.sharding.get_abstract_mesh(), spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *logical_axes: Optional[str],
                   shape: Optional[Sequence[int]] = None) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(logical_axes, shape=shape, mesh=mesh))
