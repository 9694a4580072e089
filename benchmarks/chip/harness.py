"""Runs one cell of BENCHMARK.json once and returns its result line.

Everything particular to a cell is found by name: the cell ``<config>.<traffic>``
names ``configs/<config>.json`` and ``traffic/<traffic>.json``; the
configuration names its driver, ``drivers/<driver>.py``; each per-layer
metric ``<m>`` is read by ``metrics/<m>.py``. Adding a cell, a mix or a
metric therefore adds files and edits none.

A driver module defines ``Cell(config, traffic, seed)`` with ``warm_up()``,
``solve(probe)`` -> (units of work, answer), ``work(notes, units)`` -> the
least work the metrics divide by a peak, ``check(answer)`` -> {name: (value,
limit)} and ``end_to_end(window_s, units)`` -> {metric: value}. A metric
module defines ``read(ctx)`` -> a number, or None where it finds nothing to
read.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import pathlib
import sys
import time
from typing import Callable, Optional

import jax
import numpy as np

import trace_reduce

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# host spans the traced run writes, as the trace reduction names idle gaps
SPANS = ("solve", "runtime_start", "hetero_object", "run", "barrier", "get",
         "runtime_shutdown")


class NoDevice(RuntimeError):
    """The machine lacks the chips the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(workload: str, bench: dict, here: pathlib.Path = HERE):
    """(cell, config, traffic) for the workload named in ``bench``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config_name, traffic_name = workload.split(".", 1)
    if (cell["config"], cell["traffic"]) != (config_name, traffic_name):
        raise ValueError(f"{workload} must be <config>.<traffic>")
    entry = {c["name"]: c for c in bench["configs"]}[config_name]
    config = load_json(here.parent.parent / entry["file"])
    traffic = load_json(here / "traffic" / f"{traffic_name}.json")
    return cell, config, traffic


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """The entries of ``bench[kind]`` that this cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def lookup_peaks(kind: str, path: pathlib.Path = HERE / "peaks.json") -> dict:
    table = load_json(path)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {path.name}; add its "
                       f"published peaks with their source")
    return table[kind]


def check_devices(platform: str, chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoDevice(f"no {platform}: JAX found {devices[0].platform}")
    if len(devices) != chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices


class Probe:
    """What a driver reports while it solves: counters and notes always,
    host spans around the program's calls in a traced run only."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.counters: dict = {}
        self.notes: dict = {}
        self.span_s: dict = {}

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.traced:
            yield
            return
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        n, s = self.span_s.get(name, (0, 0.0))
        self.span_s[name] = (n + 1, s + time.perf_counter() - t)

    def _wrap(self, fn: Callable, name: str,
              after: Optional[Callable] = None) -> Callable:
        def spanned(*args, **kwargs):
            with self.span(name):
                out = fn(*args, **kwargs)
            return after(out) if after else out
        return spanned

    def instrument(self, rt) -> None:
        """In a traced run, put spans around the runtime instance's calls
        and around ``get`` on each object it makes; chunk shapes are noted
        for the metrics that count work."""
        if not self.traced:
            return
        shapes = []
        self.note("chunk_shapes", shapes)

        def made(obj):
            obj.get = self._wrap(obj.get, "get")
            if obj.name.startswith("chunk"):
                shapes.append(tuple(obj.shape))
            return obj
        rt.hetero_object = self._wrap(rt.hetero_object, "hetero_object", made)
        for name in ("run", "barrier"):
            setattr(rt, name, self._wrap(getattr(rt, name), name))

    def count(self, deltas: dict) -> None:
        for k, v in deltas.items():
            self.counters[k] = self.counters.get(k, 0) + v

    def note(self, key: str, value) -> None:
        self.notes.setdefault(key, []).append(value)


class CompileCounter:
    """Counts, while it is on, the programs JAX built for a call and how many
    of them it found in the persistent cache; the rest compiled. None should
    compile inside the window."""

    def __init__(self):
        self.on = False
        self.built = self.cached = 0
        jax.monitoring.register_event_duration_secs_listener(self._built)
        jax.monitoring.register_event_listener(self._cached)

    def _built(self, event: str, duration: float, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _cached(self, event: str, **_):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.cached += 1


def memory_peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def in_use(devices) -> int:
    return sum((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in devices)


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             started: float, bench: dict, here: pathlib.Path = HERE,
             platform: str = "tpu", trace_dir: Optional[str] = None) -> dict:
    """One run of one cell. ``started`` is the host clock at process start;
    ``platform`` is the device platform the run insists on."""
    cell, config, traffic = find_cell(workload, bench, here)
    devices = check_devices(platform, cell["chips"])
    peaks = lookup_peaks(devices[0].device_kind, here / "peaks.json")
    driver = load_module(here / "drivers" / f"{config['driver']}.py",
                         f"bench_driver_{config['driver']}")
    compiles = CompileCounter()

    work = driver.Cell(config, traffic, seed)
    work.warm_up()                # compiles (or loads) every program
    gc.collect()
    setup_s = time.perf_counter() - started

    # the answer compared with the reference: one solve of the window drawn
    # from the seed, each with the same chance, holding one answer at a time
    draw = np.random.default_rng(seed)
    probe = Probe(traced=trace)
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the spans are enough
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    compiles.on = True
    solves, units, kept, kept_at, resident = 0, 0, None, 0, []
    t0 = time.perf_counter()
    while True:
        n, answer = work.solve(probe)
        units += n
        if draw.integers(solves + 1) == 0:
            kept, kept_at = answer, solves
        del answer
        solves += 1
        gc.collect()
        resident.append(in_use(devices))
        window_s = time.perf_counter() - t0
        if window_s >= seconds:
            break
    compiles.on = False
    if trace:
        jax.profiler.stop_trace()
    peak = memory_peak(devices)

    if trace:
        reduced = trace_reduce.reduce_dir(trace_dir, SPANS)
        ctx = {"iterations": units, "window_s": window_s,
               "counters": probe.counters, "notes": probe.notes,
               "spans": probe.span_s, "trace": reduced, "peaks": peaks,
               "chips": len(devices),
               "work": work.work(probe.notes, units)}
        metrics = {}
        for m in cell_metrics(bench, workload, "per_layer"):
            reader = load_module(here / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = work.end_to_end(window_s, units)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bench, workload, "end_to_end")}

    log(f"window: {solves} solves, {units} units, {window_s:.3f} s; sampled "
        f"solve {kept_at}; programs built in window {compiles.built}, "
        f"{compiles.built - compiles.cached} of them compiled; device bytes in "
        f"use after each solve {resident}")
    del probe
    gc.collect()
    checked = work.check(kept)
    correct = all(v <= lim for v, lim in checked.values())
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": solves, "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["top_gaps"]}
    result["checked"] = {k: {"value": v, "limit": lim}
                         for k, (v, lim) in checked.items()}
    for k, (v, lim) in checked.items():
        log(f"checked {k} = {v!r} limit {lim!r}")
    return result
