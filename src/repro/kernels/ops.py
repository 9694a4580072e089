"""Jit'd public wrappers for the Pallas kernels.

On the ``cpu`` platform the kernels run in interpret mode (kernel bodies
execute in Python), which is how the tests check them; on ``tpu`` they
compile to Mosaic. Any other platform has no kernel path and is refused.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.jacobi3d import jacobi3d as _jacobi3d
from repro.kernels.matmul import matmul as _matmul
from repro.kernels.ssd import ssd_chunk as _ssd_chunk


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"no Pallas kernel path for platform {backend!r}")
    return backend == "cpu"


def matmul(a, b, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _matmul(a, b, **kw)


def jacobi3d(u, lo0, hi0, lo1, hi1, lo2, hi2, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _jacobi3d(u, lo0, hi0, lo1, hi1, lo2, hi2, **kw)


def ssd_chunk(x, dt, A, B, C, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _ssd_chunk(x, dt, A, B, C, **kw)


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", _default_interpret())
    return _flash(q, k, v, **kw)
