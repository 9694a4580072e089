"""Pure-jnp oracles for the Pallas kernels (allclose targets for tests); the
Jacobi stencil's is ``apps/jacobi3d.stencil_jnp``, the path it replaces."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32)
                   ).astype(a.dtype)


def ssd_chunk_ref(x, dt, A, B, C):
    """Same contract as kernels.ssd.ssd_chunk (bc-folded, ngroups=1)."""
    dA = dt * A[None, None, :]                       # [bc,q,h]
    cs = jnp.cumsum(dA, axis=1)
    q = x.shape[1]
    diff = cs[:, :, None, :] - cs[:, None, :, :]     # [bc,l,s,h]
    mask = jnp.tril(jnp.ones((q, q), bool))
    L = jnp.where(mask[None, :, :, None], jnp.exp(diff), 0.0)
    scores = jnp.einsum("bln,bsn->bls", C, B)
    xdt = x * dt[..., None]
    y = jnp.einsum("bls,blsh,bshp->blhp", scores, L, xdt)
    decay = jnp.exp(cs[:, -1:, :] - cs)              # [bc,q,h]
    st = jnp.einsum("bsn,bsh,bshp->bhpn", B, decay * dt, x)
    return y.astype(jnp.float32), st.astype(jnp.float32)


def flash_ref(q, k, v, causal=True):
    """q: [BH,S,D]; k,v: [BH,T,D]."""
    sc = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    if causal:
        s, t = sc.shape[1], sc.shape[2]
        mask = jnp.arange(t)[None, :] <= jnp.arange(s)[:, None]
        sc = jnp.where(mask[None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bst,btd->bsd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)
