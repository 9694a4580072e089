"""Device API — the bottom layer of the tasking framework (paper §3.1.5).

Encapsulates vendor-specific device operations behind an abstract class, so
the Core Runtime never touches a backend directly. The JAX implementation
covers every XLA backend uniformly (CPU/GPU/TPU) — JAX plays the role the
paper's OpenCL-dialect kernel macro played: one kernel definition, every
backend. Hardware adaptation notes in DESIGN.md §2.

Transfer engine primitives (paper §3.2.3/§4.1.3): besides the synchronous
``upload``/``download`` pair, devices expose asynchronous variants returning
``TransferHandle``s, plus a direct device→device ``transfer`` that never
bounces through host memory — the GPU-aware-interconnect analogue. The Core
Runtime's per-device transfer queues are built on these primitives.
"""
from __future__ import annotations

import abc
import dataclasses
import os
import threading

from repro.core import sanitizer
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

# pseudo-device id for transfer sources not wrapped locally (a payload
# arriving from another rank's runtime) in interconnect observations
FOREIGN = -2


@dataclasses.dataclass
class DeviceInfo:
    device_id: int
    device_type: str            # 'cpu' | 'gpu' | 'tpu'
    memory_capacity: int        # bytes the runtime may use on this device
    name: str = ""


class TransferHandle:
    """Handle on an (a)synchronous copy. ``result()`` blocks until the data
    is resident; ``is_ready()`` polls without blocking (the PREMA
    requirement: status queries must never stall the time-slicing loop)."""

    __slots__ = ("_value", "_ready_fn")

    def __init__(self, value: Any, ready_fn: Optional[Callable[[], bool]]
                 = None):
        self._value = value
        self._ready_fn = ready_fn

    def is_ready(self) -> bool:
        return self._ready_fn() if self._ready_fn is not None else True

    def result(self) -> Any:
        v = self._value
        if hasattr(v, "block_until_ready"):
            v.block_until_ready()
        return v


class Device(abc.ABC):
    """Abstract device: (a)synchronous task launch + data management."""

    def __init__(self, info: DeviceInfo):
        self.info = info

    @abc.abstractmethod
    def upload(self, host_array: np.ndarray) -> Any: ...

    @abc.abstractmethod
    def download(self, dev_array: Any) -> np.ndarray: ...

    def download_into(self, dev_array: Any, out: np.ndarray) -> np.ndarray:
        """Copy a resident array into a caller-provided host buffer — the
        runtime's pooled D2H staging path (chunks of a device array land
        in slices of a StagingPool buffer). Backends with pinned-memory
        DMA override this; the default bounces through ``download``."""
        np.copyto(out, self.download(dev_array))
        return out

    def start_download(self, dev_array: Any) -> Any:
        """Begin copying a resident array to the host, so that a later
        ``download``/``download_into`` of it overlaps the host's own work;
        returns the array. The default starts nothing."""
        return dev_array

    @abc.abstractmethod
    def transfer_from(self, src: Optional["Device"], dev_array: Any) -> Any:
        """Copy ``dev_array`` (resident on ``src``, which may be None when
        the source device is foreign) onto this device without staging
        through host memory (paper Fig. 7: device-aware path)."""

    def upload_async(self, host_array: np.ndarray) -> TransferHandle:
        return TransferHandle(self.upload(host_array))

    def download_async(self, dev_array: Any) -> TransferHandle:
        return TransferHandle(self.download(dev_array))

    def clone(self, dev_array: Any) -> Any:
        """Private on-device copy of a resident array (no host bounce).
        Used to snapshot data that must survive buffer donation of the
        original. Backends without donation may return the array itself."""
        return dev_array

    @abc.abstractmethod
    def launch(self, kernel: Callable, args: Tuple[Any, ...],
               donate: Tuple[int, ...] = ()) -> Any: ...

    @abc.abstractmethod
    def synchronize(self, handle: Any) -> Any: ...

    @abc.abstractmethod
    def is_ready(self, handle: Any) -> bool: ...

    def completion_waiter(self, handle: Any) -> Callable[[], Any]:
        """Blocking ready-wait closure for an already-dispatched launch —
        what the progress engine's per-device completion lane runs to
        turn the handle into a completion event (the runtime never polls
        ``is_ready`` in its compute workers anymore). Backends may
        return a cheaper wait than full ``synchronize``."""
        return lambda: self.synchronize(handle)


def transfer(src_dev: Optional[Device], dst_dev: Device,
             dev_array: Any,
             observer: Optional[Callable[[int, int, int, float], None]]
             = None) -> Any:
    """Direct D2D copy: move ``dev_array`` from ``src_dev`` to ``dst_dev``
    with no host bounce. The single entry point every layer above (core
    runtime coherence walk, distributed DIRECT payload path) routes through.
    ``src_dev`` may be None when the source device is not wrapped locally
    (e.g. a payload arriving from another rank's runtime) — such sources
    are reported as ``FOREIGN``.

    ``observer(src_id, dst_id, nbytes, seconds)`` is the interconnect
    stats hook: every caller that owns an ``InterconnectModel`` passes
    its ``observe`` so the one primitive feeds all topology estimates.
    On asynchronously-dispatching backends the sample reflects dispatch +
    enqueue (a lower bound the EWMA smooths)."""
    if src_dev is not None and src_dev.info.device_id == dst_dev.info.device_id:
        return dev_array
    t0 = time.perf_counter() if observer is not None else 0.0
    out = dst_dev.transfer_from(src_dev, dev_array)
    if observer is not None:
        src_id = src_dev.info.device_id if src_dev is not None else FOREIGN
        observer(src_id, dst_dev.info.device_id,
                 int(getattr(dev_array, "nbytes", 0)),
                 time.perf_counter() - t0)
    return out


class JaxDevice(Device):
    """A single jax.Device wrapped in the Device API.

    Kernel launches go through a per-(kernel, donation) jit cache —
    the "custom allocator" analogue: donation lets XLA reuse input buffers
    in place of fresh allocations (paper §4.1.2). Async dispatch gives the
    multi-stream overlap of §4.1.3: launches return immediately and
    ``is_ready`` polls without blocking.
    """

    def __init__(self, info: DeviceInfo, jax_device: jax.Device,
                 cache_jit: bool = True):
        super().__init__(info)
        self.jax_device = jax_device
        self.cache_jit = cache_jit
        # Keyed on the kernel OBJECT (strong ref), never id(kernel): an id
        # can be recycled after the kernel is garbage-collected, silently
        # launching a stale compiled function for a new kernel.
        self._jit_cache: Dict[Tuple[Callable, Tuple[int, ...]], Callable] = {}
        self._lock = sanitizer.make_lock("Device._jit_lock")

    def upload(self, host_array: np.ndarray) -> Any:
        arr = jax.device_put(host_array, self.jax_device)
        # CPU backends may ZERO-COPY device_put (the device buffer aliases
        # the numpy one). The runtime recycles host staging buffers, so
        # upload must guarantee an independent device copy: re-put a private
        # host copy (only the jax array references it → aliasing is safe).
        if (self.info.device_type == "cpu"
                and np.may_share_memory(np.asarray(arr), host_array)):
            arr = jax.device_put(host_array.copy(), self.jax_device)
        return arr

    def download(self, dev_array: Any) -> np.ndarray:
        return np.asarray(dev_array)

    def start_download(self, dev_array: Any) -> Any:
        dev_array.copy_to_host_async()
        return dev_array

    def transfer_from(self, src: "Device", dev_array: Any) -> Any:
        # jax.device_put on a committed jax.Array issues the copy directly
        # between the two buffers (ICI/NVLink/PCIe, backend permitting) —
        # no intermediate np.ndarray is ever materialized.
        return jax.device_put(dev_array, self.jax_device)

    def clone(self, dev_array: Any) -> Any:
        import jax.numpy as jnp
        with jax.default_device(self.jax_device):
            return jnp.array(dev_array, copy=True)

    def upload_async(self, host_array: np.ndarray) -> TransferHandle:
        arr = self.upload(host_array)
        ready = arr.is_ready if hasattr(arr, "is_ready") else None
        return TransferHandle(arr, ready)

    def _get_jit(self, kernel: Callable, donate: Tuple[int, ...]) -> Callable:
        if not self.cache_jit:
            return jax.jit(kernel, donate_argnums=donate)
        key = (kernel, donate)
        with self._lock:
            fn = self._jit_cache.get(key)
            if fn is None:
                fn = jax.jit(kernel, donate_argnums=donate)
                self._jit_cache[key] = fn
        return fn

    def launch(self, kernel: Callable, args: Tuple[Any, ...],
               donate: Tuple[int, ...] = ()) -> Any:
        fn = self._get_jit(kernel, donate)
        with jax.default_device(self.jax_device):
            return fn(*args)

    def synchronize(self, handle: Any) -> Any:
        return jax.block_until_ready(handle)

    def is_ready(self, handle: Any) -> bool:
        return all(l.is_ready() for l in jax.tree.leaves(handle)
                   if hasattr(l, "is_ready"))


def device_capacity(jax_device: jax.Device, n_devices: int,
                    fraction: float = 0.75) -> int:
    """Honest per-device capacity: CPU devices split the host's physical
    memory; an accelerator must report its byte limit through
    ``memory_stats`` (GPU/TPU do), and one that reports none is refused
    rather than given a guessed size."""
    if jax_device.platform == "cpu":
        host = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
        return int(host * fraction / n_devices)
    stats = jax_device.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not limit:
        raise RuntimeError(f"{jax_device} reports no memory limit "
                           f"(memory_stats: {stats})")
    return int(limit * fraction)


def discover_devices(memory_capacity: Optional[int] = None,
                     cache_jit: bool = True) -> List[JaxDevice]:
    """One runtime Device per jax.Device. ``memory_capacity`` caps the bytes
    the runtime's memory monitor allows per device (None → honest per-device
    capacity reported by the backend, see ``device_capacity``)."""
    all_devs = jax.devices()
    devs = []
    for i, d in enumerate(all_devs):
        cap = memory_capacity if memory_capacity is not None \
            else device_capacity(d, len(all_devs))
        devs.append(JaxDevice(
            DeviceInfo(device_id=i, device_type=d.platform,
                       memory_capacity=cap, name=str(d)), d,
            cache_jit=cache_jit))
    return devs
