"""Jacobi3D through the tasking runtime.

One solve is what a user who solves once runs: a fresh ``Runtime`` with the
program's defaults, ``apps/jacobi3d.run_tasked`` over the whole grid for the
traffic's iterations, and the runtime shut down again. A window is made of
whole solves, each from the same grid, and its unit of work is the Jacobi
iteration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import grids
import harness
import reference
from repro.apps.jacobi3d import run_tasked
from repro.core import Runtime, RuntimeConfig

# counters of Runtime.stats() that the per-layer metrics read
COUNTERS = ("tasks", "replayed_tasks", "bytes_h2d", "bytes_d2h", "bytes_d2d")
# the program (jit module) that run_tasked's update task compiles to
UPDATE_MODULE = "jit_update_kernel"
F32 = 4


def update_bytes(chunk) -> int:
    """Least bytes one update task moves: it reads the chunk and its six
    faces and writes the chunk, float32."""
    s0, s1, s2 = chunk
    return F32 * (2 * s0 * s1 * s2 + 2 * (s1 * s2 + s0 * s2 + s0 * s1))


def step_work(shape) -> tuple:
    """Least (bytes, operations) of one Jacobi iteration over the grid: each
    point is read once and written once, and takes six additions and one
    division."""
    points = int(np.prod(shape))
    return 2 * F32 * points, 7 * points


def chunk_placement(rt) -> list:
    """For each of the runtime's devices, in their order, the names of the
    chunks whose device copy lives there, after checking that each recorded
    copy really is on that device's chip."""
    placed = []
    for dev in rt.devices:
        dev_id = dev.info.device_id
        names = []
        for obj in rt.residency.objects_on(dev_id):
            if not obj.name.startswith("chunk"):
                continue
            arr = obj.copies[dev_id]
            if arr.devices() != {dev.jax_device}:
                raise AssertionError(f"{obj.name} is recorded on {dev_id} "
                                     f"but lives on {arr.devices()}")
            names.append(obj.name)
        placed.append(sorted(names))
    return placed


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        if config["dtype"] != "float32":
            raise ValueError(f"jacobi_tasked runs float32, not "
                             f"{config['dtype']}")
        self.shape = tuple(config["shape"])
        self.iters = int(traffic["iters_per_solve"])
        self.od = int(traffic["over_decomposition"])
        self.limits = config["limits"]
        self.u0 = grids.uniform(self.shape, seed)

    def warm_up(self) -> None:
        """A solve of one iteration: it builds every program and transfer
        shape a full solve uses, at a fraction of the time."""
        self.solve(None, iters=1)

    def solve(self, probe, iters: int = 0):
        """One solve of ``iters`` iterations (the traffic's by default);
        returns (iterations, grid). ``probe`` None reports nothing."""
        iters = iters or self.iters
        probe = probe or harness.Probe(traced=False)
        with probe.span("solve"):
            with probe.span("runtime_start"):
                rt = Runtime(RuntimeConfig())
            try:
                probe.instrument(rt)
                before = rt.stats()
                out = run_tasked(self.u0, iters, rt,
                                 over_decomposition=self.od)
                after = rt.stats()
                probe.count({k: after[k] - before[k] for k in COUNTERS})
                if probe.traced:
                    probe.note("placement", chunk_placement(rt))
            finally:
                with probe.span("runtime_shutdown"):
                    rt.shutdown()
        return iters, out

    def work(self, notes: dict, iterations: int) -> dict:
        """What the kernels and the whole iteration have to move at least,
        for the metrics that divide by a peak; the chunk shapes are those
        the traced run saw made."""
        step_bytes, step_ops = step_work(self.shape)
        return {"update_module": UPDATE_MODULE,
                "update_bytes": [update_bytes(c)
                                 for c in notes["chunk_shapes"][0]],
                "step_bytes": step_bytes, "step_ops": step_ops,
                "iterations": iterations}

    def check(self, got: np.ndarray) -> dict:
        """The numbers compared with the plain reference, each with its
        limit."""
        gap = reference.max_abs_gap(got, self.u0, self.iters,
                                    devices=jax.devices())
        return {"max_abs_gap": (gap, self.limits["max_abs_gap"])}

    def control(self) -> dict:
        """What ``check`` would read with the reference computed in bfloat16,
        the precision below the configuration's float32, in the program's
        place."""
        return {"max_abs_gap": reference.control_gap(
            self.u0, self.iters, jnp.bfloat16, devices=jax.devices())}

    @staticmethod
    def end_to_end(window_s: float, iterations: int) -> dict:
        return {"iter_ms": 1e3 * window_s / iterations}

