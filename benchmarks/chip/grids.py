"""Seeded inputs, made on the device in slabs and brought to the host once."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# bytes of one generated slab: keeps generation's device peak small beside
# what the solves themselves hold
SLAB_BYTES = 256 << 20


def key(seed: int) -> jax.Array:
    """A PRNG key from any whole number a seed may be (more bits than 32)."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


@functools.partial(jax.jit, static_argnames="shape")
def _slab(k, i, *, shape):
    return jax.random.uniform(jax.random.fold_in(k, i), shape, jnp.float32)


def uniform(shape, seed: int, devices=None) -> np.ndarray:
    """A float32 grid of ``shape`` uniform in [0, 1) from ``seed``, the same
    whatever devices make it: slab ``i`` of planes along axis 0 comes from
    the key folded with ``i``."""
    devices = list(devices or jax.devices())
    n0 = shape[0]
    plane = int(np.prod(shape[1:])) * 4
    planes = max(p for p in range(1, n0 + 1)
                 if n0 % p == 0 and p * plane <= max(SLAB_BYTES, plane))
    out = np.empty(shape, np.float32)
    k = key(seed)
    starts = range(0, n0, planes)
    for r in range(0, len(starts), len(devices)):
        made = [(lo, _slab(jax.device_put(k, dev), lo // planes,
                           shape=(planes,) + tuple(shape[1:])))
                for lo, dev in zip(starts[r:r + len(devices)], devices)]
        for lo, slab in made:
            out[lo:lo + planes] = np.asarray(slab)
    return out
