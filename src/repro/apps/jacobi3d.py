"""Jacobi3D proxy application (paper §4.3–4.4).

Four execution modes on the same numerics:

  run_reference   — single-array jnp oracle
  run_tasked      — PREMA-style: the domain is over-decomposed into mobile
                    chunks executed as hetero_tasks with implicit
                    dependencies; halo exchange = put operations; compute and
                    halo traffic of different chunks overlap (paper Fig. 14)
  run_cluster     — distributed proxy on the message engine: slabs are
                    scattered over ranks through ``Rank.send`` (large slabs
                    ride the chunk-streamed rendezvous protocol), halo
                    planes travel as eager ``Rank.put`` operations into
                    preregistered halo objects, and the result is gathered
                    back through the same protocol — the paper's §4.3
                    distributed Jacobi on the topology-aware pipeline.
  run_cluster_elastic — run_cluster's numerics under the elastic fault-
                    tolerance runtime: slabs are mobile chunks tracked by
                    an OwnerMap, every iteration commits a checkpoint, and
                    a fault schedule (kill / revive / freeze) exercises the
                    detect → shrink → restore → resume loop live. The run
                    survives losing a rank mid-flight with a bounded stall
                    and NO restart, and the answer stays bit-identical.
  run_spmd        — production path: shard_map over a mesh axis with
                    ppermute halo exchange — the compiled TPU analogue;
                    ``bulk_sync=True`` emulates the MPI+CUDA baseline
                    (exchange, barrier, then compute), ``False`` lets XLA
                    overlap per-slab compute with the next face transfer.

Every mode but the reference sweeps through ``stencil_update``: on a TPU the
Pallas stencil (repro.kernels.jacobi3d), elsewhere the jnp one.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.core import HeteroTask, Runtime
from repro.distributed.collectives import halo_exchange_1d
from repro.distributed.handlers import handler
from repro.distributed.overdecomp import DecompPlan, plan_decomposition
from repro.kernels.jacobi3d import jacobi3d, supports


def stencil_update(u: jax.Array, lo0, hi0, lo1, hi1, lo2, hi2) -> jax.Array:
    """One Jacobi sweep over the interior given face halos (each a slab of
    thickness 1; zeros at physical boundaries).

    On a TPU a chunk the Pallas stencil ``supports`` (float32, ``Y % 8 ==
    0``, ``Z % 128 == 0``, eight planes within its VMEM budget) runs it,
    reading the chunk and its faces in place; anything else, and every other
    platform, runs ``stencil_jnp``."""
    faces = (lo0, hi0, lo1, hi1, lo2, hi2)
    if not (supports(u.shape, u.dtype)
            and all(f.dtype == u.dtype for f in faces)):
        return stencil_jnp(u, *faces)
    return jax.lax.platform_dependent(
        u, *faces, tpu=functools.partial(jacobi3d, interpret=False),
        default=stencil_jnp)


def stencil_jnp(u: jax.Array, lo0, hi0, lo1, hi1, lo2, hi2) -> jax.Array:
    """``stencil_update`` in plain jnp: the chunk padded by its faces, then
    the six shifted views summed."""
    up = jnp.pad(u, 1)
    up = up.at[0, 1:-1, 1:-1].set(lo0).at[-1, 1:-1, 1:-1].set(hi0)
    up = up.at[1:-1, 0, 1:-1].set(lo1).at[1:-1, -1, 1:-1].set(hi1)
    up = up.at[1:-1, 1:-1, 0].set(lo2).at[1:-1, 1:-1, -1].set(hi2)
    return ((up[:-2, 1:-1, 1:-1] + up[2:, 1:-1, 1:-1] +
             up[1:-1, :-2, 1:-1] + up[1:-1, 2:, 1:-1] +
             up[1:-1, 1:-1, :-2] + up[1:-1, 1:-1, 2:]) / 6.0).astype(u.dtype)


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def run_reference(u0: np.ndarray, iters: int) -> np.ndarray:
    u = jnp.asarray(u0)

    @jax.jit
    def step(u):
        z = jnp.zeros
        return stencil_jnp(
            u,
            z(u.shape[1:]), z(u.shape[1:]),
            z((u.shape[0], u.shape[2])), z((u.shape[0], u.shape[2])),
            z(u.shape[:2]), z(u.shape[:2]))

    for _ in range(iters):
        u = step(u)
    return np.asarray(u)


# ---------------------------------------------------------------------------
# PREMA-tasked over-decomposed version
# ---------------------------------------------------------------------------

def run_tasked(u0: np.ndarray, iters: int, runtime: Runtime,
               over_decomposition: int = 1) -> np.ndarray:
    """Over-decomposed Jacobi on the heterogeneous tasking runtime. Chunks
    are hetero_objects; each iteration submits per-chunk face-extraction and
    update tasks whose dependencies the runtime infers — independent chunks
    overlap automatically (the paper's Fig. 14 pipeline).

    Each chunk borrows a read-only view of its block of ``u0``, with no
    copy: its first upload stages straight from the view's strides, and
    the runtime never writes into ``u0`` (a host write request on a
    read-only copy is handed a private one)."""
    n_workers = len(runtime.devices)
    plan = plan_decomposition(u0.shape, n_workers, over_decomposition)
    with TraceAnnotation("jacobi.split"):
        chunks = {}
        for c in plan.chunks:
            block = u0[c.lo[0]:c.hi[0], c.lo[1]:c.hi[1], c.lo[2]:c.hi[2]]
            block.flags.writeable = False
            chunks[c.cid] = runtime.hetero_object(block, name=f"chunk{c.cid}")
        # halo buffers per (chunk, face)
        faces = {}
        for c in plan.chunks:
            s = c.shape
            face_shapes = {"lo0": (s[1], s[2]), "hi0": (s[1], s[2]),
                           "lo1": (s[0], s[2]), "hi1": (s[0], s[2]),
                           "lo2": (s[0], s[1]), "hi2": (s[0], s[1])}
            for tag, fs in face_shapes.items():
                faces[(c.cid, tag)] = runtime.hetero_object(
                    np.zeros(fs, u0.dtype), name=f"halo{c.cid}:{tag}")

    # kernels created once → the runtime's jit cache hits across iterations
    def make_face_kernel(tag: str):
        d = int(tag[-1])
        hi = tag.startswith("hi")

        def extract(u, out):
            idx = [slice(None)] * 3
            idx[d] = -1 if hi else 0
            return u[tuple(idx)]
        return extract

    face_kernels = {tag: make_face_kernel(tag)
                    for tag in ("lo0", "hi0", "lo1", "hi1", "lo2", "hi2")}

    def update_kernel(u, l0, h0, l1, h1, l2, h2):
        return stencil_update(u, l0, h0, l1, h1, l2, h2)

    opposite = {"lo0": "hi0", "hi0": "lo0", "lo1": "hi1", "hi1": "lo1",
                "lo2": "hi2", "hi2": "lo2"}

    for _ in range(iters):
        # 1) extract + "send" faces into the neighbour's halo buffers (put)
        for c in plan.chunks:
            nb = plan.neighbors(c.cid)
            for tag, other in nb.items():
                if other is None:
                    continue
                runtime.run(
                    face_kernels[tag],
                    [(chunks[c.cid], "r"),
                     (faces[(other, opposite[tag])], "w")],
                    name=f"halo{c.cid}->{other}")
        # 2) update each chunk from its halo buffers
        for c in plan.chunks:
            args = [(chunks[c.cid], "rw")]
            for tag in ("lo0", "hi0", "lo1", "hi1", "lo2", "hi2"):
                args.append((faces[(c.cid, tag)], "r"))
            runtime.run(update_kernel, args, name=f"update{c.cid}")
        # iteration edge: the task-graph tracer keys recurrence detection
        # on this (no-op unless cfg.trace_graphs is set) — after
        # replay_after identical sweeps the whole iteration replays as
        # fused per-chain dispatches
        runtime.step_boundary()
    runtime.barrier(timeout=600)

    out = np.empty_like(u0)
    for c in plan.chunks:
        # each chunk comes down straight into its block of the output
        chunks[c.cid].get(out=out[c.lo[0]:c.hi[0], c.lo[1]:c.hi[1],
                                  c.lo[2]:c.hi[2]])
    return out


# ---------------------------------------------------------------------------
# distributed version on the message engine (paper §4.3)
# ---------------------------------------------------------------------------
# handler-side state lives on the Rank objects themselves (one driver
# thread coordinates; handlers only deposit data and trip events)

@handler(name="jacobi_slab")
def _recv_slab(ctx, obj):
    st = ctx.rank._jacobi
    st["slab"] = obj
    st["slab_evt"].set()


@handler(name="jacobi_halo_done")
def _halo_done(ctx, obj):
    st = ctx.rank._jacobi
    with st["lock"]:
        st["halos"] += 1
        if st["halos"] >= st["halos_expected"]:
            st["halo_evt"].set()


@handler(name="jacobi_gather")
def _recv_gather(ctx, obj):
    st = ctx.rank._jacobi
    with st["lock"]:
        st["gathered"][ctx.message.user["part"]] = obj
        if len(st["gathered"]) >= st["gather_expected"]:
            st["gather_evt"].set()


def _slab_bounds(n: int, parts: int) -> List[Tuple[int, int]]:
    return [(p * n // parts, (p + 1) * n // parts) for p in range(parts)]


def run_cluster(u0: np.ndarray, iters: int, cluster, *,
                residual_every: int = 0,
                residuals: Optional[list] = None) -> np.ndarray:
    """Distributed Jacobi over ``cluster``'s ranks: axis-0 slab
    decomposition, scatter/gather through ``Rank.send`` (credit-windowed
    rendezvous streams for slabs above the eager threshold — big slabs
    never head-of-line block the halo control traffic), per-iteration
    halo planes through DIRECT ``Rank.put`` into preregistered halo
    objects (the freshly-extracted face already lives on a device, so the
    plane travels device-to-device; oversized planes would chunk-stream
    through the same rendezvous path).

    ``residual_every=k`` computes the global update-residual norm
    ``||u_new - u_old||_2`` every k iterations through a runtime
    allreduce of per-rank partial sums (``(iter, norm)`` appended to
    ``residuals``) — no slab ever travels to rank 0 for it, unlike the
    final gather."""
    ranks = cluster.ranks
    n = len(ranks)
    bounds = _slab_bounds(u0.shape[0], n)
    for i, r in enumerate(ranks):
        r._jacobi = {
            "lock": threading.Lock(), "slab": None,
            "slab_evt": threading.Event(), "halos": 0,
            "halos_expected": (1 if i > 0 else 0) + (1 if i < n - 1 else 0),
            "halo_evt": threading.Event(),
            "gathered": {}, "gather_expected": n - 1,
            "gather_evt": threading.Event(),
        }
    # scatter: rank 0 owns u0; remote slabs travel the message protocol
    for i, (lo, hi) in enumerate(bounds):
        part = np.ascontiguousarray(u0[lo:hi])
        if i == 0:
            ranks[0]._jacobi["slab"] = ranks[0].runtime.hetero_object(part)
        else:
            src = ranks[0].runtime.hetero_object(part)
            ranks[0].send(i, "jacobi_slab", src)
    for i in range(1, n):
        assert ranks[i]._jacobi["slab_evt"].wait(60), f"scatter to {i}"

    # per-rank halo objects + frozen zero faces for the untouched dims
    zeros = {}
    for i, r in enumerate(ranks):
        s = r._jacobi["slab"].shape
        rt = r.runtime
        r.register_object("jlo", rt.hetero_object(
            np.zeros((s[1], s[2]), u0.dtype)))
        r.register_object("jhi", rt.hetero_object(
            np.zeros((s[1], s[2]), u0.dtype)))
        zeros[i] = (rt.hetero_object(np.zeros((s[0], s[2]), u0.dtype)),
                    rt.hetero_object(np.zeros((s[0], s[1]), u0.dtype)))

    def lo_face(u, out):
        return u[0]

    def hi_face(u, out):
        return u[-1]

    def update(u, l0, h0, z1, z2):
        return stencil_update(u, l0, h0, z1, z1, z2, z2)

    coll = None
    if residual_every > 0:
        from repro.distributed.collectives_rt import CollectiveGroup
        coll = CollectiveGroup(cluster)

    for it in range(iters):
        res_tick = coll is not None and (it + 1) % residual_every == 0
        prev = {i: np.array(r._jacobi["slab"].get())
                for i, r in enumerate(ranks)} if res_tick else None
        for r in ranks:
            r._jacobi["halos"] = 0
            r._jacobi["halo_evt"].clear()
        # extract boundary planes + put them into the neighbours' halos
        for i, r in enumerate(ranks):
            rt, slab = r.runtime, r._jacobi["slab"]
            s = slab.shape
            if i > 0:
                f = rt.hetero_object(shape=(s[1], s[2]), dtype=u0.dtype)
                rt.run(lo_face, [(slab, "r"), (f, "w")])
                r.put(i - 1, "jhi", f, on_done="jacobi_halo_done",
                      path="direct")
            if i < n - 1:
                f = rt.hetero_object(shape=(s[1], s[2]), dtype=u0.dtype)
                rt.run(hi_face, [(slab, "r"), (f, "w")])
                r.put(i + 1, "jlo", f, on_done="jacobi_halo_done",
                      path="direct")
        for r in ranks:
            if r._jacobi["halos_expected"]:
                assert r._jacobi["halo_evt"].wait(60), "halo exchange"
        # update each slab from its (now current) halo objects
        for i, r in enumerate(ranks):
            rt, slab = r.runtime, r._jacobi["slab"]
            z1, z2 = zeros[i]
            rt.run(update, [(slab, "rw"), (r.objects["jlo"], "r"),
                            (r.objects["jhi"], "r"), (z1, "r"), (z2, "r")])
        for r in ranks:
            r.runtime.barrier(timeout=120)
        if res_tick:
            # per-rank partial ||du||^2, summed by a (tiny, eager-tree)
            # runtime allreduce — bit-identical on every member
            parts = [np.array(
                [np.sum((np.asarray(r._jacobi["slab"].get(),
                                    dtype=np.float64)
                         - prev[i]) ** 2)])
                for i, r in enumerate(ranks)]
            total = coll.allreduce(parts)[0]
            if residuals is not None:
                residuals.append((it + 1, float(np.sqrt(total[0]))))

    # gather back to rank 0 through the protocol
    for i in range(1, n):
        ranks[i].send(0, "jacobi_gather", ranks[i]._jacobi["slab"],
                      user={"part": i})
    if n > 1:
        assert ranks[0]._jacobi["gather_evt"].wait(60), "gather"
    out = np.empty_like(u0)
    out[bounds[0][0]:bounds[0][1]] = ranks[0]._jacobi["slab"].get()
    for i in range(1, n):
        lo, hi = bounds[i]
        out[lo:hi] = ranks[0]._jacobi["gathered"][i].get()
    return out


# ---------------------------------------------------------------------------
# elastic fault-tolerant version (ISSUE: ELASTIC-Recover)
# ---------------------------------------------------------------------------
# Slabs are mobile chunks keyed ("jslab", i) in an OwnerMap; halo planes
# land in per-slab objects ("jhalo", side, i) at the slab's CURRENT owner.
# The driver never assumes the world is stable: each iteration snapshots
# the elastic epoch under er.hold(), issues the halo puts against that
# snapshot, and redoes the phase from scratch if a recovery or drain
# bumped the epoch mid-exchange. Redo is safe because slabs only change
# inside the committed update phase — a re-extracted face is bitwise the
# face the first attempt extracted.

@handler(name="jacobi_eslab")
def _recv_eslab(ctx, obj):
    ctx.rank.register_object(("jslab", ctx.message.user["slab"]), obj)


@handler(name="jacobi_replica")
def _recv_replica(ctx, obj):
    """Landing half of slab replication: register the committed bytes as
    a live replica under the slab's global key (so ``ElasticRuntime``'s
    replica-first recovery finds it) and mark the (iteration, slab) pair
    arrived for the driver's replication barrier."""
    u = ctx.message.user
    old = ctx.rank.objects.get(("jslab", u["slab"]))
    if old is not None and old is not obj:
        ctx.rank.runtime.residency.forget(old)
    ctx.rank.register_object(("jslab", u["slab"]), obj)
    st = getattr(ctx.rank, "_jac_rep", None)
    if st is not None:
        with st["lock"]:
            st["got"].add((u["it"], u["slab"]))


@handler(name="jac_halo_mark")
def _halo_mark(ctx, obj):
    # obj is the preregistered halo target; None would mean the put beat
    # the registration (can't happen: registration is driver-side, before
    # the put issues) — refuse to mark rather than count lost data.
    st = getattr(ctx.rank, "_jac_halos", None)
    if st is None or obj is None:
        return
    with st["lock"]:
        st["got"].add(ctx.message.object_key)


def run_cluster_elastic(u0: np.ndarray, iters: int, cluster, *,
                        slabs: Optional[int] = None,
                        ckpt_dir: Optional[str] = None,
                        kill: Optional[Tuple[int, int]] = None,
                        revive_at: Optional[Tuple[int, int]] = None,
                        freeze: Optional[Tuple[int, int, float]] = None,
                        replicate: bool = False,
                        corrupt_links: float = 0.0,
                        corrupt_leaf_at: Optional[Tuple[int, str]] = None,
                        heartbeat_interval_s: float = 0.02,
                        heartbeat_timeout_s: float = 0.5,
                        straggler_factor: float = 25.0,
                        poll_period_s: Optional[float] = None,
                        wait_timeout_s: float = 120.0,
                        ) -> Tuple[np.ndarray, Dict[str, Any]]:
    """Distributed Jacobi that SURVIVES rank loss and stragglers mid-run.

    ``kill=(rank, it)`` kills ``rank`` after iteration ``it`` commits its
    checkpoint; ``revive_at=(rank, it)`` folds it back in with live
    rebalancing migrations; ``freeze=(rank, it, secs)`` freezes a rank's
    network (it keeps computing) so the straggler path drains chunks off
    it. Recovery restores lost slabs from the per-iteration checkpoint —
    exact committed bytes, so a faulted run matches an unfaulted one
    bit-for-bit. Returns ``(result, report)``.

    Integrity knobs (ISSUE: INTEG-Recover): ``replicate=True`` streams
    each slab's committed bytes to a buddy rank (next alive rank in the
    ring) every iteration, so recovery prefers a live replica over disk.
    ``corrupt_links=p`` bit-flips every host-staged payload on every
    directed link with probability ``p`` — the checksum layer rejects
    the flipped bytes and the reliability layer retransmits, so the run
    still converges bit-identically. ``corrupt_leaf_at=(it, key)`` flips
    one bit in that committed checkpoint leaf right after iteration
    ``it`` commits (silent storage corruption); the digest-validated
    restore path detects it and falls back to a replica or older step.
    """
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.distributed.elastic import ElasticRuntime
    from repro.distributed.mobile_object import OwnerMap, block_distribution

    ranks = cluster.ranks
    n = len(ranks)
    S = slabs or n
    bounds = _slab_bounds(u0.shape[0], S)
    owner = OwnerMap()
    for i, r in block_distribution(S, n).items():
        owner.assign(i, r)

    faults = cluster.faults
    if (kill or revive_at or freeze or corrupt_links
            or corrupt_leaf_at) and faults is None:
        faults = cluster.fault_injector()
    if kill is not None and ckpt_dir is None and not replicate:
        raise ValueError("kill schedule needs ckpt_dir or replicate=True: "
                         "lost slabs are restored from the committed "
                         "checkpoint or a live replica")
    if corrupt_leaf_at is not None and ckpt_dir is None:
        raise ValueError("corrupt_leaf_at needs ckpt_dir")
    if corrupt_links:
        for a in range(n):
            for b in range(n):
                if a != b:
                    faults.set_link(a, b, corrupt=corrupt_links)

    ckpt = (Checkpointer(ckpt_dir, keep=3, async_save=False)
            if ckpt_dir else None)

    def restore_fn(oid):
        # newest committed copy of the leaf that passes digest/shape
        # validation — a corrupted newest step falls back to an older one
        if ckpt.latest_step() is None:
            raise RuntimeError("rank loss before the first checkpoint")
        _step, arr = ckpt.restore_leaf_fallback(f"slab{oid}")
        return arr

    er = ElasticRuntime(
        cluster, owner, key_fn=lambda oid: ("jslab", oid),
        restore_fn=restore_fn if ckpt is not None else None,
        monitor=0, heartbeat_interval_s=heartbeat_interval_s,
        heartbeat_timeout_s=heartbeat_timeout_s,
        straggler_factor=straggler_factor)

    for r in ranks:
        r._jac_halos = {"lock": threading.Lock(), "got": set()}
        r._jac_rep = {"lock": threading.Lock(), "got": set()}

    # -- scatter against the initial owner map -------------------------
    for i, (lo, hi) in enumerate(bounds):
        part = np.ascontiguousarray(u0[lo:hi])
        dst = owner.owner(i)
        obj = ranks[0].runtime.hetero_object(part)
        if dst == 0:
            ranks[0].register_object(("jslab", i), obj)
        else:
            ranks[0].send(dst, "jacobi_eslab", obj, user={"slab": i})
    t_end = time.time() + wait_timeout_s
    for i in range(S):
        while ("jslab", i) not in ranks[owner.owner(i)].objects:
            assert time.time() < t_end, f"scatter of slab {i} stalled"
            time.sleep(0.002)

    # kernels created once → per-shape jit cache hits on EVERY rank, so a
    # migrated slab computes the same bits wherever it lands
    def lo_face(u, out):
        return u[0]

    def hi_face(u, out):
        return u[-1]

    def update(u, l0, h0, z1, z2):
        return stencil_update(u, l0, h0, z1, z1, z2, z2)

    zcache: Dict[Tuple[int, Tuple[int, ...]], Tuple[Any, Any]] = {}

    def zeros_for(r, s):
        z = zcache.get((r.rank, s))
        if z is None:
            z = (r.runtime.hetero_object(np.zeros((s[0], s[2]), u0.dtype)),
                 r.runtime.hetero_object(np.zeros((s[0], s[1]), u0.dtype)))
            zcache[(r.rank, s)] = z
        return z

    def ensure_halos():
        # halo targets must exist at a slab's current owner BEFORE any put
        # for this epoch issues (registration is driver-side + in-process,
        # so it happens-before the put's network delivery)
        for i in range(S):
            r = ranks[owner.owner(i)]
            s = r.objects[("jslab", i)].shape
            for side in ("lo", "hi"):
                key = ("jhalo", side, i)
                if key not in r.objects:
                    r.register_object(key, r.runtime.hetero_object(
                        np.zeros((s[1], s[2]), u0.dtype)))

    def issue_halos():
        expected = []
        for i in range(S):
            src = ranks[owner.owner(i)]
            rt = src.runtime
            slab = src.objects[("jslab", i)]
            s = slab.shape
            if i > 0:
                f = rt.hetero_object(shape=(s[1], s[2]), dtype=u0.dtype)
                rt.run(lo_face, [(slab, "r"), (f, "w")])
                src.put(owner.owner(i - 1), ("jhalo", "hi", i - 1), f,
                        on_done="jac_halo_mark", path="direct")
                expected.append((owner.owner(i - 1), ("jhalo", "hi", i - 1)))
            if i < S - 1:
                f = rt.hetero_object(shape=(s[1], s[2]), dtype=u0.dtype)
                rt.run(hi_face, [(slab, "r"), (f, "w")])
                src.put(owner.owner(i + 1), ("jhalo", "lo", i + 1), f,
                        on_done="jac_halo_mark", path="direct")
                expected.append((owner.owner(i + 1), ("jhalo", "lo", i + 1)))
        return expected

    er.start(poll_period_s)
    try:
        for it in range(iters):
            rep_expected: List[Tuple[int, int]] = []
            while True:               # redo loop: one pass per world epoch
                with er.hold():
                    epoch0 = er.epoch
                    for r in ranks:
                        with r._jac_halos["lock"]:
                            r._jac_halos["got"].clear()
                    ensure_halos()
                    expected = issue_halos()
                # wait outside the hold so the monitor can reshape the
                # world underneath us; epoch bump → redo from scratch
                t_end = time.time() + wait_timeout_s
                done = False
                while not done and er.epoch == epoch0:
                    done = all(key in ranks[dst]._jac_halos["got"]
                               for dst, key in expected)
                    if done:
                        break
                    assert time.time() < t_end, \
                        f"halo exchange stalled at iteration {it}"
                    time.sleep(0.002)
                if not done:
                    continue
                with er.hold():
                    if er.epoch != epoch0:
                        continue       # world changed after the wait; redo
                    for i in range(S):
                        r = ranks[owner.owner(i)]
                        slab = r.objects[("jslab", i)]
                        z1, z2 = zeros_for(r, slab.shape)
                        r.runtime.run(
                            update,
                            [(slab, "rw"),
                             (r.objects[("jhalo", "lo", i)], "r"),
                             (r.objects[("jhalo", "hi", i)], "r"),
                             (z1, "r"), (z2, "r")])
                    alive = set(er.controller.alive_workers())
                    for r in ranks:
                        if r.rank in alive:
                            r.runtime.barrier(timeout=wait_timeout_s)
                    if ckpt is not None:
                        ckpt.save(it, {
                            f"slab{i}": np.asarray(
                                ranks[owner.owner(i)]
                                .objects[("jslab", i)].get())
                            for i in range(S)}, block=True)
                    if replicate:
                        # stream each slab's committed bytes to its ring
                        # buddy; recovery will prefer this live replica
                        # over a disk read. Stale replicas elsewhere are
                        # dropped first — a later recovery must never
                        # resurrect an older iteration's bytes.
                        for i in range(S):
                            own = owner.owner(i)
                            cands = sorted(w for w in alive if w != own)
                            if not cands:
                                continue
                            buddy = next((w for w in cands if w > own),
                                         cands[0])
                            for r in ranks:
                                if r.rank in (own, buddy):
                                    continue
                                stale = r.objects.pop(("jslab", i), None)
                                if stale is not None:
                                    r.runtime.residency.forget(stale)
                            ranks[own].send(
                                buddy, "jacobi_replica",
                                ranks[own].objects[("jslab", i)],
                                user={"slab": i, "it": it})
                            rep_expected.append((buddy, i))
                    break              # iteration committed
            # replication barrier OUTSIDE the hold (the buddy's pump must
            # run to land the stream) and BEFORE the fault schedule: the
            # replica must exist before the rank it protects against dies
            t_end = time.time() + wait_timeout_s
            for buddy, i in rep_expected:
                while (it, i) not in ranks[buddy]._jac_rep["got"]:
                    assert time.time() < t_end, \
                        f"replica of slab {i} stalled at iteration {it}"
                    time.sleep(0.002)
            # fault schedule fires AFTER the commit point, so a restore
            # replays exactly this iteration's bytes
            if faults is not None:
                if corrupt_leaf_at is not None and it == corrupt_leaf_at[0]:
                    faults.corrupt_checkpoint_leaf(ckpt_dir, it,
                                                   corrupt_leaf_at[1])
                if kill is not None and it == kill[1]:
                    faults.kill_rank(kill[0])
                if freeze is not None and it == freeze[1]:
                    faults.freeze_rank(freeze[0], freeze[2])
                if revive_at is not None and it == revive_at[1]:
                    faults.revive_rank(revive_at[0])
                    er.grow([revive_at[0]])
    finally:
        er.close()

    report = er.report()
    report["epochs"] = er.epoch
    if faults is not None:
        report["faults"] = dict(faults.stats)
    report["integrity"] = {
        "checksum_fail": sum(r.stats["checksum_fail"] for r in ranks),
        "chunks_rejected": sum(r.stats["chunks_rejected"] for r in ranks),
        "retries": sum(r.stats["retries"] for r in ranks),
        "task_retries": sum(r.runtime.stats()["task_retries"]
                            for r in ranks),
        "lineage_recomputes": sum(r.runtime.stats()["lineage_recomputes"]
                                  for r in ranks),
        "ckpt_verify_fail": ckpt.stats["ckpt_verify_fail"] if ckpt else 0,
        "restore_fallbacks": er.stats["restore_fallbacks"],
    }
    report["collectives"] = {
        "coll_bytes_reduced": sum(
            r.stats["coll_bytes_reduced"] for r in ranks),
        "coll_chunks_in_flight_peak": max(
            r.stats["coll_chunks_in_flight_peak"] for r in ranks),
        "coll_aborts": sum(r.stats["coll_aborts"] for r in ranks),
    }
    out = np.empty_like(u0)
    for i, (lo, hi) in enumerate(bounds):
        out[lo:hi] = np.asarray(
            ranks[owner.owner(i)].objects[("jslab", i)].get())
    return out, report


# ---------------------------------------------------------------------------
# SPMD production version (shard_map + ppermute)
# ---------------------------------------------------------------------------

def make_spmd_step(mesh: Mesh, axis: str = "data", bulk_sync: bool = False):
    """Returns a jitted step: u sharded along dim 0 of [X,Y,Z] over ``axis``.
    bulk_sync=True forces the halo exchange to complete before any compute
    (optimization barrier) — the MPI+CUDA baseline schedule."""

    def local_step(u):
        lo0, hi0 = halo_exchange_1d(u, axis)
        if bulk_sync:
            u, lo0, hi0 = jax.lax.optimization_barrier((u, lo0, hi0))
        z = jnp.zeros
        return stencil_update(
            u, lo0[0], hi0[0],
            z((u.shape[0], u.shape[2]), u.dtype),
            z((u.shape[0], u.shape[2]), u.dtype),
            z((u.shape[0], u.shape[1]), u.dtype),
            z((u.shape[0], u.shape[1]), u.dtype))

    step = jax.shard_map(local_step, mesh=mesh,
                         in_specs=PS(axis), out_specs=PS(axis))
    return jax.jit(step)


def run_spmd(u0: np.ndarray, iters: int, mesh: Mesh, axis: str = "data",
             bulk_sync: bool = False) -> np.ndarray:
    step = make_spmd_step(mesh, axis, bulk_sync)
    sharding = NamedSharding(mesh, PS(axis))
    u = jax.device_put(jnp.asarray(u0), sharding)
    for _ in range(iters):
        u = step(u)
    return np.asarray(u)
