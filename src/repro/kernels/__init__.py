"""Pallas TPU kernels for the compute hot spots: matmul (the paper's DGEMM
microbenchmark), jacobi3d (the proxy app stencil), ssd_chunk (mamba2 SSD
quadratic form), flash_attention. Each has a pure-jnp oracle (in ref.py, the
stencil's in apps/jacobi3d.py) and a jit'd wrapper in ops.py (interpret mode
on the CPU platform)."""
from repro.kernels import ops, ref  # noqa: F401
