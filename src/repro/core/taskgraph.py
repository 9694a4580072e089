"""Compiled task-graph fast path: trace → compile → replay (ROADMAP 4).

The paper's single-device headline comes from driving per-task overhead
below the kernel cost; once the runtime core is correct, what dominates
small tasks is pure Python — future resolution, ledger lookups, lane
hops, dependency inference. This module removes that tax for *recurring*
DAGs (Jacobi sweeps, serve steps, microbatch train steps) the way DaCe
optimizes a dataflow graph and then emits it as a unit, and the way CUDA
graphs replay a captured stream:

  trace    ``GraphTracer`` records each ``Runtime.submit`` between two
           window boundaries (``Runtime.step_boundary()`` or
           ``Runtime.barrier()``) as a canonical node: kernel identity,
           argument topology (object slots by first occurrence), access
           modes, shapes/dtypes, device-type preference. The per-window
           structural key — kernel ids × dependency shape × dtypes and
           shapes — detects recurrence across consecutive windows.

  compile  on the ``replay_after``-th identical window the tracer waits
           for that window's (already interpreted) tasks, captures the
           scheduler's placement decisions, and compiles a
           ``TracedGraph``: maximal same-device runs of nodes fuse into
           one jitted chain each (submission order is a topological
           order, so executing chains in order is dependency-correct);
           entry transfers are pre-planned once from the residency
           ledger's replica map.

  replay   subsequent submits that match the compiled structure are
           *parked* — no pins, no dependency inference, no scheduler, no
           per-task lane hop. At the window boundary the whole DAG runs
           as one replay: entry copies issued as a batch, one dispatch
           per chain (``jax.jit`` cache hit on the persistent chain
           callable), outputs rebound to their hetero_objects, and every
           parked future resolved at once. Interior futures are elided —
           they resolve with ``None`` rather than a per-task device
           handle (the documented contract for traced windows).

  invalidate  anything the trace can't vouch for falls back to
           interpreted mode and re-traces: a submit that deviates from
           the recorded structure (different kernel / objects / access
           modes — which is also how shape changes appear, since objects
           carry their shape), eviction of a pre-planned replica
           (detected at replay; the window still executes correctly via
           the coherence walk, then drops the graph), an
           ``ElasticRuntime`` epoch bump (``Runtime.invalidate_traces``),
           or a mid-window host access (parked tasks flush through the
           interpreted path so ``request_host`` observes every write).

Nothing here runs unless ``RuntimeConfig.trace_graphs`` is set: the
tracer is opt-in per runtime, and drivers mark step edges with
``runtime.step_boundary()`` (a no-op when tracing is off).
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.core import sanitizer
from repro.core import device_api
from repro.core.hetero_object import HOST
from repro.core.hetero_task import HeteroTask, TaskState

__all__ = ["GraphTracer", "TracedGraph"]

_COMPILE_WAIT_S = 120.0


class _Node:
    """One recorded submit, canonicalized against the window's slot map."""

    __slots__ = ("kernel", "device", "device_type", "arg_slots", "modes",
                 "write_slots")

    def __init__(self, kernel, device, device_type, arg_slots, modes,
                 write_slots):
        self.kernel = kernel
        self.device = device
        self.device_type = device_type
        self.arg_slots = arg_slots        # tuple[slot] in arg order
        self.modes = modes                # tuple[Access] in arg order
        self.write_slots = write_slots    # tuple[slot], write-args in order


class _Chain:
    """A maximal same-device run of nodes fused into one jitted dispatch."""

    __slots__ = ("device", "fn", "in_slots", "out_slots", "n_tasks")

    def __init__(self, device, fn, in_slots, out_slots, n_tasks):
        self.device = device
        self.fn = fn
        self.in_slots = in_slots
        self.out_slots = out_slots
        self.n_tasks = n_tasks


def _make_chain_fn(specs, in_slots, out_slots):
    """Compose a window chain into one traceable callable. ``specs`` is
    [(kernel, arg_slots, write_slots)] in submission order; the closure
    threads slot values through an env exactly the way the interpreted
    path threads written arrays through the hetero_objects."""

    def chain_fn(*xs):
        env = dict(zip(in_slots, xs, strict=True))
        for kern, arg_slots, write_slots in specs:
            res = kern(*(env[s] for s in arg_slots))
            outs = res if isinstance(res, (tuple, list)) else (res,)
            for ws, out in zip(write_slots, outs, strict=False):
                env[ws] = out
        return tuple(env[s] for s in out_slots)

    return chain_fn


class TracedGraph:
    """A compiled recurring window: fused chains + pre-planned entries.

    ``objects`` holds the window's hetero_objects by slot (strong refs —
    replay matching is by object identity). ``entries`` lists
    ``(slot, device, expected_resident)``: the batch of input copies the
    replay issues up front, with the residency expectation captured once
    from the ledger at compile time. ``chains`` run in submission order;
    cross-chain values travel through the replay env, not through the
    objects, so objects are rebound exactly once per window."""

    __slots__ = ("key", "nodes", "objects", "chains", "entries", "replays")

    def __init__(self, key, nodes, objects, chains, entries):
        self.key = key
        self.nodes = nodes
        self.objects = objects
        self.chains = chains
        self.entries = entries
        self.replays = 0

    def __repr__(self):
        return (f"TracedGraph(tasks={len(self.nodes)}, "
                f"chains={len(self.chains)}, entries={len(self.entries)}, "
                f"replays={self.replays})")


class GraphTracer:
    """Records submit windows, detects recurrence, compiles and replays.

    Driven by three runtime hooks: ``on_submit`` (park or record),
    ``on_boundary`` (close a window: replay, compile, or advance the
    recurrence streak), and ``flush`` (a mid-window host access forces
    parked tasks through the interpreted path). All state is guarded by
    one reentrant lock; the expected producer is the driver thread, but
    ``invalidate`` may arrive from an elastic controller thread."""

    def __init__(self, runtime, replay_after: int = 3):
        self.rt = runtime
        self.replay_after = max(1, int(replay_after))
        self._lock = sanitizer.make_rlock("GraphTracer._lock")
        self._window: List[Tuple[HeteroTask, Callable]] = []
        self._prev_key: Optional[Tuple] = None
        self._streak = 0
        self._graph: Optional[TracedGraph] = None
        self._parked: List[HeteroTask] = []
        self._match_idx = 0
        # set when the current window already diverged from the armed
        # graph for a benign reason (host access flush): skip matching
        # until the next boundary but keep the graph armed
        self._deviated = False

    # -- introspection -------------------------------------------------
    def graph(self) -> Optional[TracedGraph]:
        with self._lock:
            return self._graph

    # -- runtime hooks -------------------------------------------------
    def on_submit(self, task: HeteroTask, kernel: Callable) -> bool:
        """True → the task was parked for replay (caller must not
        schedule it); False → record it and run interpreted."""
        with self._lock:
            g = self._graph
            if g is not None and not self._deviated:
                if (self._match_idx < len(g.nodes)
                        and self._matches(g.nodes[self._match_idx], task,
                                          kernel)):
                    self._parked.append(task)
                    self._match_idx += 1
                    return True
                # structural deviation (kernel/objects/modes changed —
                # shape changes surface here too, as different objects):
                # drop the graph and fall back to interpreted re-tracing
                self._invalidate_locked()
            self._window.append((task, kernel))
            return False

    def on_boundary(self) -> None:
        """Close the current window: replay a fully-matched one, compile
        on the Nth recurrence, or just advance the streak."""
        with self._lock:
            g = self._graph
            if g is not None and not self._deviated and self._parked:
                if self._match_idx == len(g.nodes):
                    with TraceAnnotation("rt.replay"):
                        self._replay_locked()
                    return
                # fewer submits than the trace expects: structure changed
                self._invalidate_locked()
            self._deviated = False
            if not self._window:
                return
            key = tuple(self._sig(t, k) for t, k in self._window)
            if key == self._prev_key:
                self._streak += 1
            else:
                self._prev_key = key
                self._streak = 1
            window, self._window = self._window, []
            if self._graph is None and self._streak >= self.replay_after:
                self._compile(window, key)

    def flush(self) -> None:
        """A host access (``request_host`` / device view / rebind) landed
        mid-window: parked tasks must become real tasks so the access
        observes their writes. The graph stays armed — matching resumes
        at the next boundary."""
        with self._lock:
            if not self._parked:
                return
            self._deviated = True
            self._release_parked_locked()

    def invalidate(self) -> None:
        """External invalidation (elastic epoch bump, manual): drop the
        compiled graph and restart recurrence detection."""
        with self._lock:
            if self._graph is not None or self._parked:
                self._invalidate_locked()
            self._prev_key = None
            self._streak = 0

    # -- internals -----------------------------------------------------
    @staticmethod
    def _sig(task: HeteroTask, kernel: Callable) -> Tuple:
        return (id(kernel), task.device_type,
                tuple((id(r.obj), r.access.name, r.obj.shape,
                       str(r.obj.dtype)) for r in task.args),
                bool(task.explicit_deps))

    def _matches(self, node: _Node, task: HeteroTask,
                 kernel: Callable) -> bool:
        if kernel is not node.kernel or task.explicit_deps:
            return False
        if task.device_type != node.device_type:
            return False
        if len(task.args) != len(node.arg_slots):
            return False
        objects = self._graph.objects
        for ref, slot, mode in zip(task.args, node.arg_slots, node.modes,
                                   strict=False):
            if ref.obj is not objects[slot] or ref.access is not mode:
                return False
        return True

    def _release_parked_locked(self) -> None:
        """Move parked tasks back onto the interpreted path, in order,
        and fold them into the recording window so the re-trace sees the
        true submit sequence."""
        parked, self._parked = self._parked, []
        self._match_idx = 0
        for t in parked:
            self._window.append((t, t.kernel))
            self.rt._enqueue(t)

    def _invalidate_locked(self) -> None:
        if self._graph is not None:
            self._graph = None
            self.rt._stats["graph_invalidations"] += 1
        self._prev_key = None
        self._streak = 0
        self._release_parked_locked()

    def _compile(self, window, key) -> None:
        """Compile the just-executed window into a TracedGraph. The
        window's tasks ran interpreted; waiting on their futures captures
        the scheduler's placement decisions and guarantees the residency
        snapshot below describes the steady state a replayed window
        starts from."""
        rt = self.rt
        tasks = [t for t, _ in window]
        try:
            for t in tasks:
                t.future.get(timeout=_COMPILE_WAIT_S)
        except BaseException:
            self._streak = 0          # failing window: don't compile it
            return
        if any(t.chosen_device is None for t in tasks):
            return
        # slots by first occurrence across the window
        slot_of: Dict[int, int] = {}
        objects: List[Any] = []
        nodes: List[_Node] = []
        for task, kernel in window:
            arg_slots, modes, write_slots = [], [], []
            for ref in task.args:
                s = slot_of.get(id(ref.obj))
                if s is None:
                    s = slot_of[id(ref.obj)] = len(objects)
                    objects.append(ref.obj)
                arg_slots.append(s)
                modes.append(ref.access)
                if ref.access.writes:
                    write_slots.append(s)
            nodes.append(_Node(kernel, task.chosen_device, task.device_type,
                               tuple(arg_slots), tuple(modes),
                               tuple(write_slots)))
        # fuse maximal same-device runs (submission order is topological)
        chains: List[_Chain] = []
        entries: List[Tuple[int, int, bool]] = []
        produced: set = set()      # slots written by earlier chains
        planned: set = set()       # (slot, device) entry pairs planned
        i = 0
        while i < len(nodes):
            dev = nodes[i].device
            j = i
            while j < len(nodes) and nodes[j].device == dev:
                j += 1
            run = nodes[i:j]
            specs, in_slots, written = [], [], set()
            for node in run:
                for s in node.arg_slots:
                    if s not in written and s not in in_slots:
                        in_slots.append(s)
                written.update(node.write_slots)
                specs.append((node.kernel, node.arg_slots,
                              node.write_slots))
            out_slots = []
            for node in run:
                for s in node.write_slots:
                    if s not in out_slots:
                        out_slots.append(s)
            for s in in_slots:
                if s not in produced and (s, dev) not in planned:
                    planned.add((s, dev))
                    entries.append(
                        (s, dev,
                         dev in rt.residency.devices_of(objects[s])))
            produced.update(written)
            chains.append(_Chain(dev, _make_chain_fn(specs, tuple(in_slots),
                                                     tuple(out_slots)),
                                 tuple(in_slots), tuple(out_slots),
                                 len(run)))
            i = j
        self._graph = TracedGraph(key, nodes, objects, chains, entries)
        self._match_idx = 0
        rt._stats["graphs_traced"] += 1

    def _replay_locked(self) -> None:
        """Execute the whole parked window as one replay dispatch."""
        rt, g = self.rt, self._graph
        parked, self._parked = self._parked, []
        self._match_idx = 0
        stale = False
        rt.residency.pin_many(g.objects)
        try:
            # pre-planned entry transfers, issued as one batch up front;
            # LRU bumps for already-resident replicas are deferred and
            # applied under a single ledger acquisition
            staged: Dict[Tuple[int, int], Any] = {}
            touched: List[Tuple[int, Any]] = []
            for slot, dev, expected_resident in g.entries:
                obj = g.objects[slot]
                # lock-free replica read: every window object is pinned
                # (no eviction) and every task touching it is parked in
                # this window (no concurrent rebind), so ``copies``
                # cannot change underneath us; a stale miss only falls
                # back to the coherence walk below
                arr = obj.copies.get(dev)
                if arr is None:
                    if expected_resident:
                        # a replica the plan counted on was evicted: the
                        # coherence walk still makes this window correct,
                        # but the plan is stale — re-trace afterwards
                        stale = True
                    arr = rt._ensure_on_device(obj, dev, will_write=False)
                else:
                    touched.append((dev, obj))
                staged[(slot, dev)] = arr
            if touched:
                rt.residency.touch_many(touched)
            # one dispatch per fused chain, in submission (= topo) order;
            # cross-chain values travel through env, not the objects
            env: Dict[int, Tuple[int, Any]] = {}
            for ch in g.chains:
                inputs = []
                for s in ch.in_slots:
                    if s in env:
                        src_dev, arr = env[s]
                        if src_dev != ch.device:
                            arr = device_api.transfer(
                                rt._device(src_dev), rt._device(ch.device),
                                arr, observer=rt.topology.observe)
                            rt._stats["transfers_d2d"] += 1
                            rt._stats["bytes_d2d"] += g.objects[s].nbytes
                    else:
                        arr = staged.get((s, ch.device))
                        if arr is None:
                            stale = True
                            arr = rt._ensure_on_device(
                                g.objects[s], ch.device, will_write=False)
                    inputs.append(arr)
                handle = rt._device(ch.device).launch(
                    ch.fn, tuple(inputs), donate=())
                outs = handle if isinstance(handle, (tuple, list)) \
                    else (handle,)
                for s, arr in zip(ch.out_slots, outs, strict=False):
                    env[s] = (ch.device, arr)
            # rebind written objects once, exactly like _launch does:
            # drop every old copy, the chain output becomes the only one.
            # Each rebind is a new generation; fused-chain outputs have no
            # per-task lineage record, so drop any stale one — a lost
            # replayed object is NOT lineage-recoverable (documented in
            # the recovery taxonomy), but the generation bump alone
            # already makes stale records unreplayable.
            written: List[Tuple[int, Any]] = []
            dropped: List[Tuple[int, Any]] = []
            for s, (dev, arr) in env.items():
                obj = g.objects[s]
                with obj.lock:
                    for sp in list(obj.copies):
                        if sp == HOST:
                            # host copies go through _drop_copy so pooled
                            # staging buffers return to the pool
                            rt._drop_copy(obj, sp)
                        else:
                            del obj.copies[sp]
                            dropped.append((sp, obj))
                    obj.copies[dev] = arr
                    obj.generation += 1
                written.append((dev, obj))
            # ledger drops/records and lineage forgets are batched: one
            # lock acquisition each for the whole window. Eviction
            # consults the ledger under pins we still hold, so the brief
            # gap between a rebind and its record only delays
            # evictability.
            rt.residency.drop_many(dropped)
            rt.residency.record_many(written)
            if rt.lineage is not None:
                rt.lineage.forget_many(obj for _d, obj in written)
        except BaseException as e:
            self._retire_parked(parked, error=e)
            self._invalidate_locked()
            return
        finally:
            rt.residency.unpin_many(g.objects)
        g.replays += 1
        rt._stats["graph_replays"] += 1
        rt._stats["replayed_tasks"] += len(parked)
        self._retire_parked(parked, error=None)
        if stale:
            self._invalidate_locked()

    def _retire_parked(self, parked, error: Optional[BaseException]) -> None:
        rt = self.rt
        with rt._lock:
            rt._tasks_pending -= len(parked)
            rt._work.notify_all()
        for t in parked:
            if error is not None:
                t.state = TaskState.FAILED
                t.future.set_error(error)
            else:
                t.state = TaskState.DONE
                t.future.set_result(None)
