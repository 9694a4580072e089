"""The device layer reports what the backend says, and refuses to guess:
capacity comes from the accelerator's own limit, readiness errors surface,
and the Pallas wrappers run on the CPU (interpreted) or the TPU only."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.device_api import device_capacity, discover_devices
from repro.kernels import ops


class _Accelerator:
    """A stand-in jax.Device on an accelerator platform."""

    platform = "tpu"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_capacity_is_the_reported_limit():
    dev = _Accelerator({"bytes_limit": 16 << 30, "bytes_in_use": 0})
    assert device_capacity(dev, 4, fraction=0.5) == 8 << 30


@pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 1}])
def test_capacity_refuses_an_accelerator_without_a_limit(stats):
    with pytest.raises(RuntimeError, match="no memory limit"):
        device_capacity(_Accelerator(stats), 1)


def test_cpu_capacity_splits_host_memory():
    cpu = jax.devices("cpu")[0]
    one, two = device_capacity(cpu, 1), device_capacity(cpu, 2)
    assert one > 0 and abs(one - 2 * two) <= 1


def test_is_ready_raises_what_the_handle_raises():
    class Broken:
        def is_ready(self):
            raise RuntimeError("device lost")

    dev = discover_devices(memory_capacity=1 << 20)[0]
    assert dev.is_ready([jnp.ones(3), 2.0])
    with pytest.raises(RuntimeError, match="device lost"):
        dev.is_ready(Broken())


@pytest.mark.parametrize("backend", ["gpu", "rocm"])
def test_kernels_refuse_platforms_without_a_kernel_path(monkeypatch,
                                                         backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    a = jnp.ones((128, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="no Pallas kernel path"):
        ops.matmul(a, a)
