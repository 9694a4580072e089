"""Transfer engine (paper §3.2.3 + §4.1.3): direct D2D coherence moves,
argument prefetch pipeline, per-device transfer queues, indexed scheduler
ready queues, and staging/request pool recycling.

conftest.py forces a 2-device CPU view, so every test here exercises real
cross-device movement in-process.
"""
import threading

import jax
import numpy as np
import pytest

from repro.core import (HOST, HeteroTask, Runtime, RuntimeConfig, TaskState)
from repro.core.device_api import discover_devices, transfer
from repro.core.scheduler import (SCHEDULERS, FifoScheduler,
                                  LeastLoadedScheduler,
                                  LocalityAwareScheduler,
                                  RoundRobinScheduler)


class _RoundRobinNoSteal(RoundRobinScheduler):
    """Deterministic cross-device placement for the D2D chain test: without
    stealing, a task indexed to device 1 always runs on device 1."""
    steals = False


SCHEDULERS.setdefault("_test_rr_nosteal", _RoundRobinNoSteal)


def _two_device_rt(**overrides) -> Runtime:
    cfg = RuntimeConfig(memory_capacity=1 << 28, **overrides)
    rt = Runtime(cfg)
    if len(rt.devices) < 2:
        rt.shutdown()
        pytest.skip("needs >= 2 (virtual) devices")
    return rt


# ---------------------------------------------------------------------------
# direct device-to-device path
# ---------------------------------------------------------------------------

def test_device_api_transfer_roundtrip():
    devs = discover_devices(memory_capacity=1 << 28)
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    host = np.arange(256, dtype=np.float32).reshape(16, 16)
    on0 = devs[0].upload(host)
    on1 = transfer(devs[0], devs[1], on0)
    np.testing.assert_array_equal(devs[1].download(on1), host)


def test_ensure_on_device_prefers_d2d():
    """With a device copy present and no host copy, the coherence walk must
    move data device→device — zero D2H and zero extra H2D traffic."""
    with _two_device_rt() as rt:
        x = rt.hetero_object(np.arange(64, dtype=np.float32))
        rt._ensure_on_device(x, 0, will_write=False)
        h2d_before = rt.stats()["transfers_h2d"]
        with x.lock:
            rt._drop_copy(x, HOST)      # device 0 now holds the only copy
        rt._ensure_on_device(x, 1, will_write=False)
        s = rt.stats()
        assert s["transfers_d2d"] == 1
        assert s["bytes_d2d"] == x.nbytes
        assert s["transfers_h2d"] == h2d_before   # no re-upload
        assert s["transfers_d2h"] == 0            # and no host bounce
        np.testing.assert_array_equal(x.get(),
                                      np.arange(64, dtype=np.float32))


def test_d2d_disabled_falls_back_to_host_staging():
    with _two_device_rt(d2d=False) as rt:
        x = rt.hetero_object(np.ones(64, dtype=np.float32))
        rt._ensure_on_device(x, 0, will_write=False)
        with x.lock:
            rt._drop_copy(x, HOST)
        rt._ensure_on_device(x, 1, will_write=False)
        s = rt.stats()
        assert s["transfers_d2d"] == 0
        assert s["transfers_d2h"] == 1      # staged: device→host→device
        np.testing.assert_array_equal(x.get(), 1.0)


def test_cross_device_producer_consumer_chain_uses_d2d():
    """Acceptance: a producer→consumer chain spanning two devices moves the
    intermediate via the D2D path with no D2H+H2D bounce for that hop."""
    with _two_device_rt(scheduler="_test_rr_nosteal") as rt:
        x = rt.hetero_object(np.full((32, 32), 2.0, np.float32))
        y = rt.hetero_object(shape=(32, 32), dtype=np.float32)
        t1 = rt.run(lambda v: v + 1.0, [(x, "rw")])           # → device 0
        t2 = rt.run(lambda a, out: a * 10.0, [(x, "r"), (y, "w")])  # → dev 1
        rt.barrier()
        assert t1.chosen_device != t2.chosen_device, \
            (t1.chosen_device, t2.chosen_device)
        s = rt.stats()
        assert s["transfers_d2d"] >= 1
        assert s["transfers_d2h"] == 0      # the hop never touched host
        assert s["bytes_d2h"] == 0
        np.testing.assert_allclose(y.get(), 30.0)
        np.testing.assert_allclose(x.get(), 3.0)


def test_coherence_after_mixed_d2d_and_host_writes():
    """D2D replication then a host write must invalidate device copies;
    subsequent device reads see the host data (MESI-like single rule)."""
    with _two_device_rt() as rt:
        x = rt.hetero_object(np.zeros(16, dtype=np.float32))
        rt.run(lambda v: v + 5.0, [(x, "rw")])
        rt.barrier()
        # replicate across both devices via the D2D path
        rt._ensure_on_device(x, 0, will_write=False)
        rt._ensure_on_device(x, 1, will_write=False)
        # host write invalidates every device copy
        fut = x.request_host(write=True)
        arr = fut.get(5)
        arr[...] = 7.0
        x.release()
        assert x.valid_spaces() == {HOST}
        rt.run(lambda v: v * 2.0, [(x, "rw")])
        rt.barrier()
        np.testing.assert_allclose(x.get(), 14.0)


# ---------------------------------------------------------------------------
# argument prefetch pipeline + pool recycling
# ---------------------------------------------------------------------------

def test_prefetch_pipeline_counts_hits_and_recycles_futures():
    """Every staged argument copy is accounted either as a hit (transfer
    completed during the previous task's compute) or a stall (claimed
    early but still awaited) — the pipeline must have engaged for this
    workload of non-resident arguments."""
    with _two_device_rt(prefetch=True) as rt:
        objs = [rt.hetero_object(np.ones((64, 64), np.float32))
                for _ in range(30)]
        for o in objs:
            rt.run(lambda v: (v @ v.T).astype(v.dtype), [(o, "rw")])
        rt.barrier()
        s = rt.stats()
        assert s["prefetch_hits"] + s["prefetch_stalls"] > 0, s
        # consumed transfer futures must return to the request pool
        assert len(rt.futures._free) > 0
        for o in objs:
            np.testing.assert_allclose(o.get(), 64.0)


def test_prefetch_disabled_counts_nothing():
    with _two_device_rt(prefetch=False) as rt:
        x = rt.hetero_object(np.ones(8, np.float32))
        for _ in range(5):
            rt.run(lambda v: v + 1, [(x, "rw")])
        rt.barrier()
        s = rt.stats()
        assert s["prefetch_hits"] == 0
        assert s["prefetch_stalls"] == 0
        assert s["prefetch_misses"] == 0
        np.testing.assert_allclose(x.get(), 6.0)


def test_staging_pool_buffers_are_recycled():
    """Regression (seed leak): StagingPool.release was never called, so the
    pool missed forever. Dropping a pooled host copy must recycle it."""
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        for _ in range(4):
            c = rt.hetero_object(shape=(32, 32), dtype=np.float32)
            rt.run(lambda v: v + 1.0, [(c, "w")])
            rt.barrier()
            np.testing.assert_allclose(c.get(), 1.0)
        assert rt.stats()["staging_hits"] > 0, rt.stats()


def test_chunked_host_upload_through_staging_pool():
    """Uploads above staging_chunk_bytes stream through pooled buffers and
    still produce a bit-exact device copy."""
    with Runtime(RuntimeConfig(memory_capacity=1 << 28,
                               staging_chunk_bytes=1 << 12)) as rt:
        data = np.random.default_rng(0).random((64, 64)).astype(np.float32)
        x = rt.hetero_object(data.copy())
        rt.run(lambda v: v * 1.0, [(x, "rw")])
        rt.barrier()
        np.testing.assert_allclose(x.get(), data, rtol=1e-6)
        assert rt.staging.hits + rt.staging.misses > 1   # chunked acquires


def _lent(grid, layout):
    """What a caller lends an object: a read-only view of columns 16..63
    of ``grid`` (strided), or a read-only contiguous copy of them."""
    view = grid[:, 16:64]
    if layout == "contiguous":
        view = np.ascontiguousarray(view)
    view.flags.writeable = False
    return view


def test_write_request_on_a_lent_view_gets_a_private_copy():
    grid = np.random.default_rng(1).random((64, 96), dtype=np.float32)
    before = grid.copy()
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        x = rt.hetero_object(_lent(grid, "strided"))
        arr = x.request_host(write=True).get(5)
        assert arr.flags.writeable
        assert not np.shares_memory(arr, grid)
        arr[...] = -1.0
        x.release()
        np.testing.assert_array_equal(x.get(), -1.0)
    np.testing.assert_array_equal(grid, before)


@pytest.mark.parametrize("layout, strided", [("strided", 1),
                                             ("contiguous", 0)])
def test_strided_upload_stages_from_the_strides(layout, strided):
    # 64 rows of 192 B: 1 KiB pieces are 5 rows, 13 pieces
    grid = np.random.default_rng(2).random((64, 96), dtype=np.float32)
    cfg = RuntimeConfig(memory_capacity=1 << 28, staging_chunk_bytes=1 << 10)
    with Runtime(cfg) as rt:
        x = rt.hetero_object(_lent(grid, layout))
        before = rt.stats()
        on_device = rt._ensure_on_device(x, 0, will_write=False)
        after = rt.stats()
        assert after["h2d_strided"] - before["h2d_strided"] == strided
        assert after["staging_hits"] + after["staging_misses"] - (
            before["staging_hits"] + before["staging_misses"]) == 13
        want = rt._device(0).upload(np.ascontiguousarray(grid[:, 16:64]))
        np.testing.assert_array_equal(np.asarray(on_device),
                                      np.asarray(want))


# ---------------------------------------------------------------------------
# HeteroObject.get(out=...): download into the caller's buffer
# ---------------------------------------------------------------------------

# whole numbers: every result below is exact in float32
DATA = np.arange(64 * 48, dtype=np.float32).reshape(64, 48)


def _object(rt, where):
    """An object whose only valid copy is on a device, on the host, or
    nowhere (never written)."""
    if where == "unwritten":
        return rt.hetero_object(shape=DATA.shape, dtype=DATA.dtype)
    x = rt.hetero_object(DATA.copy())
    if where == "device":
        rt.run(lambda v: v * 2.0 + 1.0, [(x, "rw")])
        rt.barrier()
        assert HOST not in x.valid_spaces()
    return x


def _out(layout):
    """A host array of DATA's shape: fresh, or a strided view of a larger
    array whose other elements are NaN."""
    if layout == "contiguous":
        return np.empty_like(DATA), None
    big = np.full((2 * DATA.shape[0], DATA.shape[1] + 5), np.nan,
                  np.float32)
    return big[::2, 3:3 + DATA.shape[1]], big


@pytest.mark.parametrize("layout", ["contiguous", "strided"])
@pytest.mark.parametrize("where", ["device", "host", "unwritten"])
def test_get_into_equals_get(where, layout):
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        x = _object(rt, where)
        out, big = _out(layout)
        got = x.get(out=out)
        assert got is out
        np.testing.assert_array_equal(out, x.get())
        if big is not None:
            # only the view was written
            assert np.isnan(big).sum() == big.size - DATA.size


@pytest.mark.parametrize("layout", ["contiguous", "strided"])
def test_get_into_downloads_in_pieces(layout):
    # 64 rows of 192 B: 1 KiB pieces are 5 rows, 13 pieces
    cfg = RuntimeConfig(memory_capacity=1 << 28, staging_chunk_bytes=1 << 10)
    with Runtime(cfg) as rt:
        x = _object(rt, "device")
        (dev,) = {rt._device(s) for s in x.valid_spaces()}
        calls, started = [], []
        plain, start = dev.download_into, dev.start_download
        dev.download_into = lambda a, o: calls.append(o.shape) or plain(a, o)
        dev.start_download = lambda a: started.append(a.shape) or start(a)
        out, _ = _out(layout)
        x.get(out=out)
        dev.download_into, dev.start_download = plain, start
        assert len(calls) == 13
        assert sum(shape[0] for shape in calls) == DATA.shape[0]
        assert started == calls     # every piece was started ahead
        np.testing.assert_array_equal(out, DATA * 2.0 + 1.0)


@pytest.mark.parametrize("shape, dtype", [((64, 47), np.float32),
                                          ((64, 48), np.float64)])
def test_get_into_refuses_another_shape_or_dtype(shape, dtype):
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        x = _object(rt, "device")
        with pytest.raises(ValueError, match="get\\(out=\\)"):
            x.get(out=np.empty(shape, dtype))
        assert not rt.residency.pinned(x)


def test_get_into_keeps_no_host_copy_and_no_staging_buffer():
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        x = _object(rt, "device")
        before = rt.stats()
        x.get(out=np.empty_like(DATA))
        after = rt.stats()
        assert HOST not in x.valid_spaces()
        assert (after["staging_hits"], after["staging_misses"]) == \
            (before["staging_hits"], before["staging_misses"])


@pytest.mark.parametrize("where, downloads", [("device", 1), ("host", 0),
                                              ("unwritten", 0)])
def test_get_into_counts_its_download(where, downloads):
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        x = _object(rt, where)
        before = rt.stats()
        x.get(out=np.empty_like(DATA))
        after = rt.stats()
        rise = {k: after[k] - before[k]
                for k in ("transfers_d2h", "bytes_d2h", "d2h_direct")}
        assert rise == {"transfers_d2h": downloads,
                        "bytes_d2h": downloads * DATA.nbytes,
                        "d2h_direct": downloads}


def _held_writer(rt, x, gate):
    """Submit a writer task of ``x`` that runs until ``gate`` is set."""
    def held(a):
        gate.wait(10)
        return a + 1.0

    def kernel(v):
        return jax.pure_callback(held, jax.ShapeDtypeStruct(v.shape,
                                                            v.dtype), v)
    return rt.run(kernel, [(x, "rw")])


def test_get_into_waits_for_the_writer_in_flight():
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        x = _object(rt, "device")
        gate = threading.Event()
        _held_writer(rt, x, gate)
        out = np.zeros_like(DATA)
        reader = threading.Thread(target=x.get, kwargs={"out": out})
        reader.start()
        reader.join(0.3)
        assert reader.is_alive()        # waiting for the writer
        gate.set()
        reader.join(10)
        assert not reader.is_alive()
        np.testing.assert_array_equal(out, DATA * 2.0 + 2.0)


def test_get_into_timeout_releases_its_pin():
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        x = _object(rt, "device")
        gate = threading.Event()
        _held_writer(rt, x, gate)
        with pytest.raises(TimeoutError):
            x.get(out=np.empty_like(DATA), timeout=0.05)
        assert x.host_pins == 0
        gate.set()
        rt.barrier(timeout=10)
        assert not rt.residency.pinned(x)   # the writer's pin alone was left


def test_get_into_releases_its_pin_for_a_later_writer():
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        x = _object(rt, "device")
        x.get(out=np.empty_like(DATA))
        assert not rt.residency.pinned(x) and x.host_pins == 0
        rt.run(lambda v: v - 1.0, [(x, "rw")])
        rt.barrier(timeout=10)
        np.testing.assert_array_equal(x.get(out=np.empty_like(DATA)),
                                      DATA * 2.0)


# ---------------------------------------------------------------------------
# indexed scheduler ready queues
# ---------------------------------------------------------------------------

def _task(device_type=None):
    t = HeteroTask()
    t.device(device_type)
    t.state = TaskState.READY
    return t


def test_fifo_overflow_is_shared_and_ordered():
    s = FifoScheduler({0: "cpu", 1: "cpu"})
    tasks = [_task() for _ in range(4)]
    for t in tasks:
        s.push(t)
    assert len(s) == 4
    got, dev = s.pop(1)
    assert got is tasks[0] and dev == 1     # O(1) head pop, any device
    got, dev = s.pop(0)
    assert got is tasks[1] and dev == 0


def test_least_loaded_places_per_device_at_push():
    s = LeastLoadedScheduler({0: "cpu", 1: "cpu"})
    tasks = [_task() for _ in range(4)]
    for t in tasks:
        s.push(t)
    # 4 untyped tasks spread 2/2 over the indexed queues
    assert s.queued[0] == 2 and s.queued[1] == 2
    got, dev = s.pop(0)
    assert dev == 0 and s.queued[0] == 1


def test_idle_device_steals_oldest():
    s = LeastLoadedScheduler({0: "cpu", 1: "cpu"})
    s.load[1] = 10                  # device 1 looks busy → all go to 0
    t1, t2 = _task(), _task()
    s.push(t1)
    s.push(t2)
    assert s.queued[0] == 2
    got, dev = s.pop(1)             # idle device 1 steals the oldest
    assert got is t1 and dev == 1
    assert s.queued[0] == 1


def test_locality_scheduler_does_not_steal():
    s = LocalityAwareScheduler({0: "cpu", 1: "cpu"})
    t = _task()
    s.push(t)
    placed = next(d for d in (0, 1) if s.queued[d] == 1)
    other = 1 - placed
    assert s.pop(other) is None     # no stealing: locality is preserved
    got, dev = s.pop(placed)
    assert got is t and dev == placed


def test_device_type_restricted_task_waits_in_overflow():
    s = LeastLoadedScheduler({0: "cpu", 1: "cpu"})
    t = _task(device_type="tpu")    # no eligible device present
    s.push(t)
    assert s.pop(0) is None and s.pop(1) is None and s.pop() is None
    assert len(s) == 1


def test_peek_and_assign_hooks():
    s = FifoScheduler({0: "cpu"})
    t1, t2 = _task(), _task()
    s.push(t1)
    s.push(t2)
    assert s.peek(0) is t1          # peek does not remove
    assert len(s) == 2
    got, dev = s.assign(0)          # assign removes, like pop
    assert got is t1 and dev == 0
    assert s.peek(0) is t2


def test_indexed_pop_scales_flat():
    """Smoke for the O(1) claim: draining 20k tasks through hinted pops
    must not show the seed's O(n²) full-queue rescans (which took minutes
    at this size)."""
    import time
    s = LeastLoadedScheduler({0: "cpu", 1: "cpu"})
    for _ in range(20000):
        s.push(_task())
    t0 = time.perf_counter()
    n = 0
    while s.pop(n % 2) is not None:
        n += 1
    assert n == 20000
    assert time.perf_counter() - t0 < 5.0


# ---------------------------------------------------------------------------
# honest device capacity + jit cache keying
# ---------------------------------------------------------------------------

def test_discover_devices_reports_positive_capacity():
    devs = discover_devices()
    assert devs and all(d.info.memory_capacity > 0 for d in devs)
    with open("/proc/meminfo") as f:
        total = int(f.readline().split()[1]) * 1024
    assert all(d.info.memory_capacity <= total for d in devs)
    # explicit override still wins
    devs = discover_devices(memory_capacity=12345)
    assert all(d.info.memory_capacity == 12345 for d in devs)


def test_jit_cache_keys_on_kernel_object():
    dev = discover_devices(memory_capacity=1 << 28)[0]

    def k1(x):
        return x + 1

    def k2(x):
        return x + 2

    f1 = dev._get_jit(k1, ())
    f2 = dev._get_jit(k2, ())
    assert f1 is not f2
    assert dev._get_jit(k1, ()) is f1          # cache hit on same object
    # the cache holds a strong ref: the key can never be a recycled id()
    assert any(k is k1 for k, _ in dev._jit_cache)
