"""Train-step factory with over-decomposition (microbatch) support.

The paper's over-decomposition insight — split the domain into more chunks
than processing elements so transfers pipeline behind compute — maps to
microbatched gradient accumulation on TPU: the per-microbatch backward's
gradient reduce-scatters/all-reduces overlap with the next microbatch's
compute under XLA's latency-hiding scheduler, and activation memory drops by
the over-decomposition factor.

``over_decompose=1`` is the paper-faithful "no over-decomposition" baseline
(one monolithic batch, synchronous reduction at the end).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.model_zoo import Model
from repro.train.optimizer import (AdamWConfig, TrainState,
                                   adamw_update, init_opt_state)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: AdamWConfig = AdamWConfig()
    over_decompose: int = 1      # microbatches per step (paper: OD level)
    z_loss: float = 0.0
    # int8 + error-feedback compression of the cross-pod gradient reduction
    # (multi-pod meshes only; see train/compression.py)
    compress_pod_grads: bool = False


def make_loss_fn(model: Model):
    def loss_fn(params, batch):
        x, _, aux = model.apply(params, batch, mode="train")
        ce = model.loss(params, x, batch["labels"])
        return ce + aux, {"ce": ce, "aux": aux}
    return loss_fn


def make_train_step(model: Model, tcfg: TrainConfig, param_axes=None
                    ) -> Callable[[TrainState, Dict[str, jax.Array]],
                                  Tuple[TrainState, Dict[str, jax.Array]]]:
    """param_axes: optional logical-axes tree (from layers.unbox) — used by
    the compressed-gradient path to keep in-pod shardings across the
    partially-manual shard_map boundary."""
    loss_fn = make_loss_fn(model)
    grad_fn = jax.grad(loss_fn, has_aux=True)
    od = tcfg.over_decompose

    def _compressed_grads(state, batch):
        """Gradients with the cross-pod reduction compressed (int8 + EF):
        shard_map manual over 'pod' only; in-pod sharding stays automatic
        and the compressed payload rides an all_gather."""
        from jax.sharding import PartitionSpec as PS
        from repro.models.sharding import active_mesh
        from repro.train.compression import compressed_pmean_tree
        assert state.ef is not None, \
            "compress_pod_grads needs EF residuals: init_train_state(..., " \
            "ef_pods=mesh.shape['pod'])"

        def body(params, batch_loc, residuals):
            from repro.models.sharding import constrain
            g, m = grad_fn(params, batch_loc)
            res_in = jax.tree.map(lambda r: r[0], residuals)
            g, new_res = compressed_pmean_tree(g, "pod", res_in)
            if param_axes is not None:
                g = jax.tree.map(lambda leaf, ax: constrain(leaf, *ax),
                                 g, param_axes)
            m = jax.tree.map(lambda v: jax.lax.pmean(v, "pod"), m)
            return g, m, jax.tree.map(lambda r: r[None], new_res)

        return jax.shard_map(
            body, mesh=active_mesh(),
            in_specs=(PS(), jax.tree.map(lambda _: PS("pod"), batch),
                      PS("pod")),
            out_specs=(PS(), PS(), PS("pod")),
            axis_names={"pod"},
            # scan carries inside the model start as pod-invariant constants;
            # vma tracking would require pcast at every scan init — the
            # gathered-mean output is replicated by construction instead
            check_vma=False,
        )(state.params, batch, state.ef)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        new_ef = state.ef
        if tcfg.compress_pod_grads and od == 1:
            grads, metrics, new_ef = _compressed_grads(state, batch)
        elif od == 1:
            grads, metrics = grad_fn(state.params, batch)
        else:
            def split(x):
                return x.reshape((od, x.shape[0] // od) + x.shape[1:])
            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                acc, met = carry
                g, m = grad_fn(state.params, mb)
                acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), acc, g)
                met = jax.tree.map(lambda a, b: a + b, met, m)
                return (acc, met), None

            acc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            met0 = {"ce": jnp.zeros((), jnp.float32),
                    "aux": jnp.zeros((), jnp.float32)}
            (grads, metrics), _ = jax.lax.scan(body, (acc0, met0), micro)
            grads = jax.tree.map(lambda g: g / od, grads)
            metrics = jax.tree.map(lambda m: m / od, metrics)
        new_state, opt_metrics = adamw_update(tcfg.opt, state, grads)
        new_state = dataclasses.replace(new_state, ef=new_ef)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = metrics["ce"] + metrics["aux"]
        return new_state, metrics

    return train_step


def runtime_allreduce(group, grad_trees, average: bool = True):
    """Gradient sync over the message-driven runtime (ROADMAP item 2).

    ``grad_trees`` is one gradient pytree per group member (identical
    treedef/leaf shapes — each member's local gradients). Leaves are
    flattened and concatenated into one vector per member so a single
    collective moves the whole gradient set — large models take the
    pipelined chunked ring, small ones the eager binomial tree — then the
    summed (or averaged) vector is split back into the original pytree
    structure. Bit-deterministic: every member unflattens the *same*
    reduced vector, so replicas agree exactly.

    Returns one reduced pytree per member, in group-member order.
    """
    import numpy as np

    if len(grad_trees) != len(group.members):
        raise ValueError(
            f"expected {len(group.members)} gradient trees, "
            f"got {len(grad_trees)}")
    leaves0, treedef = jax.tree.flatten(grad_trees[0])
    shapes = [np.asarray(leaf).shape for leaf in leaves0]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    packed = []
    for tree in grad_trees:
        leaves = jax.tree.flatten(tree)[0]
        if len(leaves) != len(leaves0):
            raise ValueError("gradient trees disagree on structure")
        packed.append(np.concatenate(
            [np.asarray(leaf).reshape(-1) for leaf in leaves]))
    reduced = group.allreduce(packed, average=average)
    outs = []
    for vec in reduced:
        leaves, off = [], 0
        for shape, size in zip(shapes, sizes):
            leaves.append(vec[off:off + size].reshape(shape))
            off += size
        outs.append(jax.tree.unflatten(treedef, leaves))
    return outs


def init_train_state(model: Model, key, ef_pods: int = 0) -> TrainState:
    from repro.models.layers import unbox
    params, _ = unbox(model.init(key))
    ef = None
    if ef_pods:
        ef = jax.tree.map(
            lambda p: jnp.zeros((ef_pods,) + p.shape, jnp.float32), params)
    return TrainState(params=params, opt=init_opt_state(params), ef=ef)


def abstract_train_state(model: Model) -> TrainState:
    """TrainState of ShapeDtypeStructs — for AOT lowering (dry-run)."""
    def go():
        from repro.models.layers import unbox
        params, _ = unbox(model.init(jax.random.PRNGKey(0)))
        return TrainState(params=params, opt=init_opt_state(params))
    return jax.eval_shape(go)
