"""PREMA distributed layer: messaging protocol, put/get, owner map,
over-decomposition, elastic control, Jacobi3D end-to-end."""
import threading
import time

import numpy as np
import pytest

from repro.core import Runtime, RuntimeConfig
from repro.distributed import (Cluster, ElasticController, OwnerMap,
                               block_distribution, handler, microbatch_plan,
                               plan_decomposition, rebalance_greedy)
from repro.apps.jacobi3d import (run_cluster, run_reference, run_spmd,
                                 run_tasked)

_received = {}
_lock = threading.Lock()


@handler(name="test_recv")
def _recv_handler(ctx, obj):
    with _lock:
        _received[ctx.message.src] = None if obj is None else obj.get()


@handler(name="test_pong")
def _pong_handler(ctx, obj):
    ctx.send(ctx.message.src, "test_recv", obj)


@handler(name="put_done")
def _put_done(ctx, obj):
    with _lock:
        _received["put_done"] = True


def _wait_for(pred, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        with _lock:
            if pred():
                return True
        time.sleep(0.005)
    return False


@pytest.fixture()
def cluster():
    cfg = RuntimeConfig(memory_capacity=1 << 26)
    with Cluster(2, cfg) as c:
        _received.clear()
        yield c


def test_handler_invocation_no_payload(cluster):
    cluster.ranks[0].send(1, "test_recv")
    assert _wait_for(lambda: 0 in _received)
    assert _received[0] is None


def test_hetero_object_payload_roundtrip(cluster):
    """mp_send with a hetero_object payload → handler sees the data; the
    two-phase metadata+payload protocol runs underneath."""
    data = np.arange(4096, dtype=np.float32).reshape(64, 64)
    obj = cluster.ranks[0].runtime.hetero_object(data)
    cluster.ranks[0].send(1, "test_pong", obj)
    assert _wait_for(lambda: 1 in _received)
    np.testing.assert_allclose(_received[1], data)


def test_small_message_inline_path(cluster):
    """≤512B payloads ride inside the metadata message (paper §4.2.3)."""
    data = np.arange(8, dtype=np.float32)       # 32 bytes → inline
    obj = cluster.ranks[0].runtime.hetero_object(data)
    cluster.ranks[0].send(1, "test_recv", obj)
    assert _wait_for(lambda: 0 in _received)
    np.testing.assert_allclose(_received[0], data)


def test_put_overwrites_remote_object(cluster):
    target = cluster.ranks[1].runtime.hetero_object(
        np.zeros((32,), np.float32))
    cluster.ranks[1].register_object("tgt", target)
    src = cluster.ranks[0].runtime.hetero_object(
        np.full((32,), 7.0, np.float32))
    cluster.ranks[0].put(1, "tgt", src, on_done="put_done")
    assert _wait_for(lambda: _received.get("put_done"))
    np.testing.assert_allclose(target.get(), 7.0)


def test_direct_path_device_payload_no_host_staging(cluster):
    """path='direct' (§3.2.3 Fig. 7): a device-resident payload travels as a
    device array and lands via one Device API transfer; both ends account
    the traffic as D2D, not staged."""
    data = np.arange(4096, dtype=np.float32).reshape(64, 64)
    rt0 = cluster.ranks[0].runtime
    obj = rt0.hetero_object(data)
    rt0.run(lambda v: v + 1.0, [(obj, "rw")])   # leaves a device-only copy
    rt0.barrier()
    cluster.ranks[0].send(1, "test_recv", obj, path="direct")
    assert _wait_for(lambda: 0 in _received)
    np.testing.assert_allclose(_received[0], data + 1.0)
    assert cluster.ranks[0].stats["bytes_d2d"] >= data.nbytes
    assert cluster.ranks[1].stats["bytes_d2d"] >= data.nbytes
    assert cluster.ranks[0].stats["bytes_staged"] == 0


def test_direct_send_survives_subsequent_donating_writer(cluster):
    """Regression: a DIRECT send snapshots the device copy; a writer task
    submitted right after must not delete the payload via buffer donation
    (the send pins the view, and the payload is a private clone)."""
    data = np.arange(4096, dtype=np.float32).reshape(64, 64)
    rt0 = cluster.ranks[0].runtime
    for trial in range(5):
        _received.pop(0, None)
        obj = rt0.hetero_object(data.copy())
        rt0.run(lambda v: v + 1.0, [(obj, "rw")])
        rt0.barrier()
        cluster.ranks[0].send(1, "test_recv", obj, path="direct")
        # donation-eligible writer racing the in-flight snapshot
        rt0.run(lambda v: v * 0.0, [(obj, "rw")])
        rt0.barrier()
        assert _wait_for(lambda: 0 in _received), f"trial {trial}: lost"
        np.testing.assert_allclose(_received[0], data + 1.0,
                                   err_msg=f"trial {trial}")


@handler(name="test_keep")
def _keep_handler(ctx, obj):
    with _lock:
        _received["kept"] = obj


def test_direct_payload_lands_on_consumer_device(cluster):
    """Consumer-routed delivery (ROADMAP follow-up d): a DIRECT payload
    with a consumer_device hint must land on that device — not on the
    historical hardwired device 0."""
    rt1 = cluster.ranks[1].runtime
    if len(rt1.devices) < 2:
        pytest.skip("needs >= 2 (virtual) devices")
    data = np.arange(4096, dtype=np.float32).reshape(64, 64)
    rt0 = cluster.ranks[0].runtime
    obj = rt0.hetero_object(data)
    rt0.run(lambda v: v + 1.0, [(obj, "rw")])   # leaves a device-only copy
    rt0.barrier()
    cluster.ranks[0].send(1, "test_keep", obj, path="direct",
                          consumer_device=1)
    assert _wait_for(lambda: "kept" in _received)
    landed = _received["kept"]
    assert landed.resident_devices() == {1}, landed.valid_spaces()
    np.testing.assert_allclose(landed.get(), data + 1.0)


def test_direct_payload_route_to_registration(cluster):
    """The receiver-side route_to(handler, device) registration routes
    DIRECT payloads without any sender-side hint."""
    rt1 = cluster.ranks[1].runtime
    if len(rt1.devices) < 2:
        pytest.skip("needs >= 2 (virtual) devices")
    cluster.ranks[1].route_to("test_keep", 1)
    try:
        data = np.full((64, 64), 3.0, np.float32)
        rt0 = cluster.ranks[0].runtime
        obj = rt0.hetero_object(data)
        rt0.run(lambda v: v * 2.0, [(obj, "rw")])
        rt0.barrier()
        cluster.ranks[0].send(1, "test_keep", obj, path="direct")
        assert _wait_for(lambda: "kept" in _received)
        assert _received["kept"].resident_devices() == {1}
    finally:
        cluster.ranks[1].routes.clear()


def test_invalid_consumer_hint_falls_through_to_route(cluster):
    """A consumer_device naming a nonexistent device must not shadow the
    receiver's route_to registration (documented fall-through chain)."""
    rt1 = cluster.ranks[1].runtime
    if len(rt1.devices) < 2:
        pytest.skip("needs >= 2 (virtual) devices")
    cluster.ranks[1].route_to("test_keep", 1)
    try:
        data = np.full((64, 64), 5.0, np.float32)
        rt0 = cluster.ranks[0].runtime
        obj = rt0.hetero_object(data)
        rt0.run(lambda v: v + 1.0, [(obj, "rw")])
        rt0.barrier()
        cluster.ranks[0].send(1, "test_keep", obj, path="direct",
                              consumer_device=99)
        assert _wait_for(lambda: "kept" in _received)
        assert _received["kept"].resident_devices() == {1}
    finally:
        cluster.ranks[1].routes.clear()


def test_direct_payload_fallback_is_least_loaded(cluster):
    """With no consumer known, the landing device comes from the residency
    ledger (least pressure, then fewest bytes resident) — loading device 0
    with resident bytes must steer the payload to device 1."""
    rt1 = cluster.ranks[1].runtime
    if len(rt1.devices) < 2:
        pytest.skip("needs >= 2 (virtual) devices")
    ballast = rt1.hetero_object(np.ones((128, 128), np.float32))
    rt1._ensure_on_device(ballast, 0, will_write=False)   # device 0 heavier
    data = np.arange(1024, dtype=np.float32)
    rt0 = cluster.ranks[0].runtime
    obj = rt0.hetero_object(data)
    rt0.run(lambda v: v + 1.0, [(obj, "rw")])
    rt0.barrier()
    cluster.ranks[0].send(1, "test_keep", obj, path="direct")
    assert _wait_for(lambda: "kept" in _received)
    assert _received["kept"].resident_devices() == {1}


def test_direct_path_host_only_falls_back_to_staged(cluster):
    """A direct send of an object with no device copy degrades gracefully
    to the host-staged protocol."""
    data = np.arange(1024, dtype=np.float32)
    obj = cluster.ranks[0].runtime.hetero_object(data)
    cluster.ranks[0].send(1, "test_recv", obj, path="direct")
    assert _wait_for(lambda: 0 in _received)
    np.testing.assert_allclose(_received[0], data)
    assert cluster.ranks[0].stats["bytes_staged"] >= data.nbytes


def test_get_remote_object(cluster):
    src_obj = cluster.ranks[1].runtime.hetero_object(
        np.full((16,), 3.0, np.float32))
    cluster.ranks[1].register_object("src", src_obj)
    cluster.ranks[0].get(1, "src", "test_recv")
    assert _wait_for(lambda: 1 in _received)
    np.testing.assert_allclose(_received[1], 3.0)


# ---------------------------------------------------------------------------
# owner map / over-decomposition / elastic
# ---------------------------------------------------------------------------

def test_block_distribution_balanced():
    d = block_distribution(16, 4)
    counts = {r: sum(1 for v in d.values() if v == r) for r in range(4)}
    assert all(c == 4 for c in counts.values())


def test_rebalance_moves_from_hot_rank():
    owner = OwnerMap()
    for i in range(8):
        owner.assign(i, 0 if i < 6 else 1)
    loads = {0: 6.0, 1: 2.0}
    plan = rebalance_greedy(loads, owner, {i: 1.0 for i in range(8)})
    assert plan, "expected at least one migration"
    assert all(src == 0 and dst == 1 for _, src, dst in plan)
    c0 = len(owner.owned_by(0))
    assert 3 <= c0 <= 5


def test_elastic_shrink_reassigns_dead_chunks():
    owner = OwnerMap()
    for i in range(12):
        owner.assign(i, i % 3)
    ec = ElasticController([0, 1, 2], heartbeat_timeout=0.01)
    ec.heartbeat(0)
    ec.heartbeat(1)
    ec.health[2].last_heartbeat -= 1.0   # rank 2 went silent
    dead = ec.detect_failures()
    assert dead == [2]
    plan = ec.shrink_plan(owner, dead)
    assert len(plan) == 4
    assert not owner.owned_by(2)


def test_straggler_mitigation_drains_slow_rank():
    owner = OwnerMap()
    for i in range(8):
        owner.assign(i, i % 2)
    ec = ElasticController([0, 1])
    ec.heartbeat(0, slowdown=4.0)   # rank 0 is 4x slower
    ec.heartbeat(1, slowdown=1.0)
    plan = ec.straggler_plan(owner)
    assert plan and all(src == 0 for _, src, dst in plan)


def test_decomposition_geometry():
    plan = plan_decomposition((32, 16, 16), n_workers=2, over_decomposition=2)
    assert len(plan.chunks) == 4
    covered = np.zeros((32, 16, 16), bool)
    for c in plan.chunks:
        covered[c.lo[0]:c.hi[0], c.lo[1]:c.hi[1], c.lo[2]:c.hi[2]] = True
    assert covered.all()
    # neighbor symmetry
    for c in plan.chunks:
        for tag, other in plan.neighbors(c.cid).items():
            if other is None:
                continue
            opp = {"lo": "hi", "hi": "lo"}[tag[:2]] + tag[2]
            assert plan.neighbors(other)[opp] == c.cid


def test_microbatch_plan():
    assert microbatch_plan(256, 4) == [64, 64, 64, 64]


# ---------------------------------------------------------------------------
# Jacobi3D end-to-end (paper §4.3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("od", [1, 2, 4])
def test_jacobi_tasked_matches_reference(od):
    rng = np.random.default_rng(0)
    u0 = rng.random((16, 8, 8)).astype(np.float32)
    want = run_reference(u0, 3)
    with Runtime(RuntimeConfig(memory_capacity=1 << 26)) as rt:
        got = run_tasked(u0, 3, rt, over_decomposition=od)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("od", [1, 4, 8])
def test_jacobi_tasked_leaves_the_callers_grid_untouched(od):
    """Chunks split along axes 1 and 2 borrow strided read-only views of
    u0; with 128-byte staging pieces every chunk uploads piecewise from its
    strides, and u0 comes back bitwise as it was."""
    u0 = np.random.default_rng(5).random((4, 16, 32)).astype(np.float32)
    before = u0.copy()
    want = run_reference(u0, 3)
    cfg = RuntimeConfig(memory_capacity=1 << 26, staging_chunk_bytes=1 << 7)
    with Runtime(cfg) as rt:
        plan = plan_decomposition(u0.shape, len(rt.devices), od)
        assert plan.chunk_grid[0] == 1
        got = run_tasked(u0, 3, rt, over_decomposition=od)
        # at least once a chunk: one read on both devices at once uploads
        # to each
        assert rt.stats()["h2d_strided"] >= len(plan.chunks)
    assert u0.flags.writeable
    np.testing.assert_array_equal(u0, before)
    np.testing.assert_array_equal(got, want)


def test_jacobi_cluster_matches_reference():
    """The distributed Jacobi proxy on the message engine (scatter via
    send, halos via put, gather via send) matches the oracle."""
    rng = np.random.default_rng(2)
    u0 = rng.random((16, 8, 8)).astype(np.float32)
    want = run_reference(u0, 3)
    with Cluster(2, RuntimeConfig(memory_capacity=1 << 26)) as c:
        got = run_cluster(u0, 3, c)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_jacobi_cluster_large_slabs_ride_rendezvous():
    """With slabs above the eager threshold the scatter/gather legs use
    the chunk-streamed rendezvous protocol — numerics must be identical."""
    rng = np.random.default_rng(3)
    u0 = rng.random((32, 32, 32)).astype(np.float32)   # 64 KB slabs
    want = run_reference(u0, 2)
    cfg = RuntimeConfig(memory_capacity=1 << 28, eager_threshold=16 << 10,
                        chunk_bytes=16 << 10)
    with Cluster(2, cfg) as c:
        got = run_cluster(u0, 2, c)
        assert c.ranks[0].stats["rendezvous"] >= 1     # scatter leg
        assert c.ranks[1].stats["rendezvous"] >= 1     # gather leg
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bulk_sync", [False, True])
def test_jacobi_spmd_matches_reference(bulk_sync):
    from repro.launch.mesh import make_smoke_mesh
    rng = np.random.default_rng(1)
    u0 = rng.random((8, 8, 8)).astype(np.float32)
    want = run_reference(u0, 3)
    mesh = make_smoke_mesh(1, 1)
    got = run_spmd(u0, 3, mesh, axis="data", bulk_sync=bulk_sync)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
