"""Production mesh construction + sharding resolution for program states.

``make_production_mesh`` is a function (never a module-level constant) so that
importing this module does not touch JAX device state.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.models.sharding import resolve_spec


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the models place activations with
    ``with_sharding_constraint`` (``models.sharding.constrain``), which
    only names Auto axes."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(data: int = 1, model: int = 1) -> Mesh:
    return make_mesh((data, model), ("data", "model"))


# ---------------------------------------------------------------------------
# Spec builders
# ---------------------------------------------------------------------------

def param_specs(abs_params, axes_tree, mesh: Mesh):
    """NamedShardings for a param tree given its logical axes tree."""
    return jax.tree.map(
        lambda sds, ax: NamedSharding(
            mesh, resolve_spec(ax, shape=sds.shape, mesh=mesh)),
        abs_params, axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def zero_shard(spec: PS, shape: Tuple[int, ...], mesh: Mesh,
               zero_axes: Tuple[str, ...] = ("data",)) -> PS:
    """Add ZeRO-1 sharding: place ``zero_axes`` on the first unsharded dim
    whose size divides. Leaves the spec unchanged if nothing fits."""
    za = tuple(a for a in zero_axes if a in mesh.shape)
    if not za:
        return spec
    zsize = int(np.prod([mesh.shape[a] for a in za]))
    parts = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for p in parts:
        if p is None:
            continue
        used.update(p if isinstance(p, tuple) else (p,))
    if any(a in used for a in za):
        return spec
    for i, (p, s) in enumerate(zip(parts, shape)):
        if p is None and s % zsize == 0 and s > 0:
            parts[i] = za if len(za) > 1 else za[0]
            return PS(*parts)
    return spec


def opt_specs(abs_state, axes_tree, mesh: Mesh, zero: bool = True):
    """Shardings for a TrainState: params get their natural specs; m/v/master
    additionally get ZeRO-1 sharding over the data axis."""
    p_specs = param_specs(
        jax.tree.map(lambda x: x, abs_state.params), axes_tree, mesh)

    def zspec(sds, ax):
        spec = resolve_spec(ax, shape=sds.shape, mesh=mesh)
        if zero:
            spec = zero_shard(spec, sds.shape, mesh)
        return NamedSharding(mesh, spec)

    is_ax = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    m_specs = jax.tree.map(zspec, abs_state.opt.m, axes_tree, is_leaf=is_ax)
    v_specs = jax.tree.map(zspec, abs_state.opt.v, axes_tree, is_leaf=is_ax)
    w_specs = jax.tree.map(zspec, abs_state.opt.master, axes_tree,
                           is_leaf=is_ax)
    from repro.train.optimizer import AdamWState, TrainState
    return TrainState(
        params=p_specs,
        opt=AdamWState(step=NamedSharding(mesh, PS()), m=m_specs, v=v_specs,
                       master=w_specs))


def batch_specs(shape_kind: str, mesh: Mesh, global_batch: int,
                seq_shard_kv: bool = False) -> Dict[str, NamedSharding]:
    """Input shardings for train/prefill/decode batches."""
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dsize = int(np.prod([mesh.shape[a] for a in data_axes]))
    baxes = data_axes if global_batch % dsize == 0 else None
    if baxes is not None and len(baxes) == 1:
        baxes = baxes[0]
    b = PS(baxes)
    return {"batch": b, "scalar": PS()}


def cache_specs(abs_cache, mesh: Mesh, cfg, *, seq_shard: bool = False,
                seq_axis: Optional[str] = None):
    """Shardings for a KV/recurrent cache pytree.

    Leaf layouts (by layer kind and role):
      attn k/v   : [..., B, T, K, D]  (stacked leading layer dims optional)
      ssd conv   : [..., B, W-1, C]    (replicated over model — DP-only SSD)
      ssd state  : [..., B, H, P, N]
      rglru conv : [..., B, W-1, lru]  (lru dim shards over model)
      rglru state: [..., B, lru]
    Batch shards over the data axes when divisible; otherwise (``seq_shard``)
    the attention T dim shards over 'data' (long-context decode).
    """
    from repro.configs.base import RGLRU, SSD

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dsize = int(np.prod([mesh.shape[a] for a in data_axes]))
    msize = mesh.shape.get("model", 1)
    baxes = data_axes if len(data_axes) > 1 else data_axes[0]
    _, rem = (cfg.n_layers // len(cfg.layer_pattern),
              tuple(cfg.layer_pattern[:cfg.n_layers % len(cfg.layer_pattern)]))

    def kind_of(path) -> str:
        keys = []
        for p in path:
            if hasattr(p, "key"):
                keys.append(p.key)
            elif hasattr(p, "idx"):
                keys.append(p.idx)
        if keys and keys[0] == "periods":
            return cfg.layer_pattern[keys[1]]
        if keys and isinstance(keys[0], str) and keys[0].startswith("rem_"):
            return rem[int(keys[0][4:])]
        return "global_attn"  # encdec decoder self/cross caches

    def leaf_spec(path, sds):
        role = None
        for p in reversed(path):
            if hasattr(p, "key"):
                role = p.key
                break
        kind = kind_of(path)
        shape, nd = sds.shape, len(sds.shape)
        parts: list = [None] * nd
        if role in ("k", "v"):
            b_dim, t_dim, k_dim = nd - 4, nd - 3, nd - 2
            if shape[b_dim] % dsize == 0:
                parts[b_dim] = baxes
            elif seq_shard and "data" in mesh.shape and \
                    shape[t_dim] % mesh.shape["data"] == 0:
                parts[t_dim] = "data"
            if seq_axis is not None and parts[t_dim] is None \
                    and seq_axis in mesh.shape \
                    and shape[t_dim] % mesh.shape[seq_axis] == 0:
                parts[t_dim] = seq_axis
            if shape[k_dim] % msize == 0 and msize > 1 \
                    and seq_axis != "model":
                parts[k_dim] = "model"
        else:
            b_dim = nd - (3 if role == "conv" else
                          4 if role == "state" and kind == SSD else 2)
            b_dim = max(b_dim, 0)
            if shape[b_dim] % dsize == 0:
                parts[b_dim] = baxes
            if kind == RGLRU and shape[-1] % msize == 0 and msize > 1 \
                    and nd - 1 != b_dim:
                parts[-1] = "model"
        return NamedSharding(mesh, PS(*parts))

    return jax.tree_util.tree_map_with_path(leaf_spec, abs_cache)
