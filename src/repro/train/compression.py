"""Cross-pod gradient compression (distributed-optimization trick).

At pod scale the inter-pod links are the slowest hop, and the gradient
all-reduce across pods is the traffic that rides them. We compress exactly
that hop: int8 block-quantized payloads are all-gathered over the ``pod``
axis and averaged after dequantization, with error-feedback residuals so
the quantization error re-enters the next step's gradients (EF-style —
preserves convergence). Inter-pod gradient bytes drop ≈8× vs an f32
ring all-reduce (int8 payload + one f32 scale per 256-block vs 2× f32).

Integration: the gradient computation runs inside a ``shard_map`` that is
*manual only over the pod axis*; data/model axes stay automatic so GSPMD
still handles in-pod reductions. See ``train_step.make_train_step`` with
``TrainConfig(compress_pod_grads=True)``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

BLOCK = 256


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array, int]:
    """Symmetric int8 quantization, blocked along the LAST axis only —
    leading dims keep their GSPMD sharding (flattening would force XLA to
    all-gather model-sharded gradients before quantizing).
    Returns (q int8 [..., n_blocks, BLOCK], scales f32 [..., n_blocks], pad).
    """
    if x.ndim == 0:
        x = x[None]
    last = x.shape[-1]
    pad = (-last) % BLOCK
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]).astype(jnp.float32)
    blocks = xp.reshape(x.shape[:-1] + (-1, BLOCK))
    scale = jnp.max(jnp.abs(blocks), axis=-1) / 127.0
    safe = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / safe[..., None]), -127,
                 127).astype(jnp.int8)
    return q, scale, pad


def dequantize_int8(q: jax.Array, scale: jax.Array, shape,
                    dtype) -> jax.Array:
    deq = q.astype(jnp.float32) * scale[..., None]
    lead = q.shape[:-2]
    flat_last = deq.reshape(lead + (-1,))
    last = shape[-1] if shape else 1
    out = flat_last[..., :last]
    return out.reshape(shape).astype(dtype)


def compressed_pmean(x: jax.Array, axis_name: str,
                     residual: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Quantized mean-reduce over a (manual) mesh axis with error feedback.

    Returns (mean over axis of x, new local residual). The communicated
    payload is the int8 blocks + f32 block scales (all-gather), then the
    mean is reconstructed locally — the compressible formulation of an
    all-reduce."""
    orig_shape = x.shape
    if x.ndim == 0:
        x = x[None]
    n = jax.lax.axis_size(axis_name)
    xin = x.astype(jnp.float32)
    if residual is not None:
        xin = xin + residual.reshape(x.shape)
    q, scale, _ = quantize_int8(xin)
    local_deq = dequantize_int8(q, scale, x.shape, jnp.float32)
    new_residual = (xin - local_deq).reshape(orig_shape)
    qg = jax.lax.all_gather(q, axis_name)    # [n, ..., blocks, BLOCK] i8
    sg = jax.lax.all_gather(scale, axis_name)   # [n, ..., blocks]
    total = jnp.sum(qg.astype(jnp.float32) * sg[..., None], axis=0)
    deq_total = total.reshape(q.shape[:-2] + (-1,))[..., :x.shape[-1]]
    mean = (deq_total.reshape(orig_shape) / n).astype(x.dtype)
    return mean, new_residual


def compressed_pmean_tree(grads, axis_name: str, residuals=None):
    """Tree-wide compressed_pmean. residuals: matching tree of f32 (or None
    on step 0). Returns (mean grads, new residual tree)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    res_leaves = [None] * len(leaves) if residuals is None else \
        jax.tree_util.tree_leaves(residuals)
    outs, news = [], []
    for g, r in zip(leaves, res_leaves):
        m, nr = compressed_pmean(g, axis_name, r)
        outs.append(m)
        news.append(nr)
    return (jax.tree_util.tree_unflatten(treedef, outs),
            jax.tree_util.tree_unflatten(treedef, news))
