"""Heterogeneous tasking runtime: dependency, coherence, scheduler, memory
invariants — including hypothesis property tests on random task DAGs."""
import threading
import time

import numpy as np
import pytest

try:        # hypothesis is optional: only the DAG property test needs it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import (HOST, HeteroTask, Runtime,  # noqa: E402
                        RuntimeConfig, TaskState)


def add_one(x, out):
    return x + 1.0


def scale(x, out):
    return x * 2.0


def combine(a, b, out):
    return a + b


@pytest.fixture()
def rt():
    r = Runtime(RuntimeConfig(memory_capacity=1 << 28))
    yield r
    r.shutdown()


def test_raw_dependency_order(rt):
    """Writer → reader executes in order (RAW)."""
    x = rt.hetero_object(np.zeros((8, 8), np.float32))
    y = rt.hetero_object(shape=(8, 8), dtype=np.float32)
    z = rt.hetero_object(shape=(8, 8), dtype=np.float32)
    rt.run(add_one, [(x, "r"), (y, "w")])       # y = x+1 = 1
    rt.run(scale, [(y, "r"), (z, "w")])         # z = 2y = 2
    rt.barrier()
    np.testing.assert_allclose(z.get(), 2.0)


def test_implicit_chain_is_sequential(rt):
    """N rw tasks on one object must serialize: result is exact."""
    x = rt.hetero_object(np.zeros((4,), np.float32))
    for _ in range(20):
        rt.run(lambda v: v + 1.0, [(x, "rw")])
    rt.barrier()
    np.testing.assert_allclose(x.get(), 20.0)


def test_war_blocks_writer(rt):
    x = rt.hetero_object(np.ones((4,), np.float32))
    y = rt.hetero_object(shape=(4,), dtype=np.float32)
    t_read = rt.run(scale, [(x, "r"), (y, "w")])
    t_write = rt.run(lambda v: v * 0.0, [(x, "rw")])
    rt.barrier()
    # reader saw the pre-write value
    np.testing.assert_allclose(y.get(), 2.0)
    np.testing.assert_allclose(x.get(), 0.0)


def test_explicit_dependency(rt):
    order = []
    lock = threading.Lock()
    a = rt.hetero_object(np.zeros((2,), np.float32))
    b = rt.hetero_object(np.zeros((2,), np.float32))

    def mark(tag):
        def k(v):
            with lock:
                order.append(tag)
            return v
        return k

    t1 = HeteroTask("first")
    t1.arg(a).rw()
    t2 = HeteroTask("second")
    t2.arg(b).rw()
    t2.add_dependency(t1)
    # submit in REVERSE order; explicit dep must still serialize
    rt.submit(t2, mark("second"))
    time.sleep(0.02)
    rt.submit(t1, mark("first"))
    rt.barrier()
    assert order == ["first", "second"]


def test_host_pin_blocks_writer(rt):
    x = rt.hetero_object(np.ones((4,), np.float32))
    fut = x.request_host()
    arr = fut.get(5)
    np.testing.assert_allclose(arr, 1.0)
    x.release()
    rt.run(lambda v: v + 1, [(x, "rw")])
    rt.barrier()
    np.testing.assert_allclose(x.get(), 2.0)


def test_write_invalidates_other_copies(rt):
    x = rt.hetero_object(np.ones((4,), np.float32))
    rt.run(lambda v: v + 1, [(x, "rw")])
    rt.barrier()
    # after a device write, host copy must be refreshed on access
    np.testing.assert_allclose(x.get(), 2.0)
    np.testing.assert_allclose(x.get(), 2.0)


def test_lru_offload_under_pressure():
    """Tiny memory budget forces evictions but never corrupts data."""
    cap = 4 * 64 * 64 * 4 + 128   # ~4 objects of 16KB
    with Runtime(RuntimeConfig(memory_capacity=cap)) as rt:
        objs = [rt.hetero_object(np.full((64, 64), i, np.float32))
                for i in range(10)]
        for o in objs:
            rt.run(lambda v: v + 1, [(o, "rw")])
        rt.barrier()
        for i, o in enumerate(objs):
            np.testing.assert_allclose(o.get(), i + 1)
        assert rt.stats()["evictions"] > 0


@pytest.mark.parametrize("sched", ["fifo", "least_loaded", "locality",
                                   "round_robin"])
def test_all_schedulers_complete(sched):
    with Runtime(RuntimeConfig(scheduler=sched,
                               memory_capacity=1 << 28)) as rt:
        x = rt.hetero_object(np.zeros((16,), np.float32))
        for _ in range(10):
            rt.run(lambda v: v + 1, [(x, "rw")])
        rt.barrier()
        np.testing.assert_allclose(x.get(), 10.0)


if HAVE_HYPOTHESIS:
    _dag_decorators = [
        settings(max_examples=15, deadline=None),
        given(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 4),
                                 st.booleans()), min_size=1, max_size=25))]
else:
    _dag_decorators = [pytest.mark.skip(reason="hypothesis not installed")]


def _apply(decorators):
    def wrap(fn):
        for d in reversed(decorators):
            fn = d(fn)
        return fn
    return wrap


@_apply(_dag_decorators)
def test_random_dag_equals_sequential(ops_list):
    """Property: any random read/write program gives results identical to
    sequential execution (the paper's correctness guarantee)."""
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        objs = [rt.hetero_object(np.full((4,), float(i), np.float32))
                for i in range(5)]
        model = [np.full((4,), float(i), np.float32) for i in range(5)]
        for src, dst, extra in ops_list:
            if src == dst:
                rt.run(lambda v: v * 2.0 + 1.0, [(objs[src], "rw")])
                model[src] = model[src] * 2.0 + 1.0
            else:
                # kernel reads a and the CURRENT b (rw), returns a + b
                rt.run(lambda a, b: a + b,
                       [(objs[src], "r"), (objs[dst], "rw")])
                model[dst] = model[src] + model[dst]
        rt.barrier()
        for i in range(5):
            np.testing.assert_allclose(objs[i].get(), model[i], rtol=1e-6)


def test_device_type_targeting(rt):
    """A task targeted at the present device type runs; unknown types have no
    eligible device and stay queued (we only check the positive path)."""
    x = rt.hetero_object(np.ones((4,), np.float32))
    t = rt.run(lambda v: v + 1, [(x, "rw")],
               device_type=rt.devices[0].info.device_type)
    rt.barrier()
    assert t.state == TaskState.DONE
    np.testing.assert_allclose(x.get(), 2.0)


def test_stats_and_staging_pool(rt):
    x = rt.hetero_object(np.ones((32, 32), np.float32))
    for _ in range(3):
        rt.run(lambda v: v + 1, [(x, "rw")])
    rt.barrier()
    s = rt.stats()
    assert s["tasks"] == 3
    assert s["bytes_h2d"] >= x.nbytes
