"""repro — reproduction of "Runtime Support for Performance Portability on
Heterogeneous Distributed Platforms" on the JAX/XLA stack."""
