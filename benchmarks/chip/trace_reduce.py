"""From a JAX profiler trace to the numbers the per-layer metrics read.

The trace's device planes (``/device:TPU:<n>``) carry one line of the
operations that ran on the chip and one of the programs (jit modules) they
belong to. The host plane carries the benchmark's own spans, written with
``jax.profiler.TraceAnnotation`` around the program's calls. Both are on one
clock.

The window is the stretch from the first ``solve`` span's start to the last
one's end. A chip is busy while any operation runs on it; the rest of the
window is idle, cut into gaps, and each gap is put down to the innermost
span of the host that holds its middle, or to ``solve`` itself, or to
``outside`` where no span does.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "solve"
# a program's event is named "<module>(<program id>)"
MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")
# an operation's event is named by its whole HLO line; keep "<op> <shape>"
OP_NAME = re.compile(r"^(%?[\w.\-]+) = (\S+)")
TOP = 10


def xplane_path(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {trace_dir}")
    return found[0]


def load(path: str, spans: Iterable[str]) -> dict:
    """Raw events: per device plane its ops and modules as (name, start,
    end) in seconds, and the host spans named in ``spans``."""
    from jax.profiler import ProfileData
    spans = set(spans)
    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].extend(
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events)
            devices[plane.name] = {"ops": lines[OPS_LINE],
                                   "modules": lines[MODULES_LINE]}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if e.name in spans)
    return {"devices": devices, "spans": host}


def union(intervals: np.ndarray) -> np.ndarray:
    """Merged, sorted [start, end) rows of an (n, 2) array."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0])]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    ends = reach[np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], axis=1)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def innermost(points: np.ndarray, spans: List[Tuple[str, float, float]]
              ) -> List[str]:
    """For each time in ``points``, the name of the shortest span holding
    it, or ``outside``."""
    names = np.full(len(points), "outside", dtype=object)
    length = np.full(len(points), np.inf)
    for name, lo, hi in spans:
        a, b = np.searchsorted(points, [lo, hi])
        sel = slice(a, b)
        shorter = length[sel] > hi - lo
        names[sel] = np.where(shorter, name, names[sel])
        length[sel] = np.where(shorter, hi - lo, length[sel])
    return list(names)


def reduce(raw: dict) -> dict:
    """Busy and window seconds (busy averaged over chips), per-module
    (count, seconds) summed over chips, the top operations and the idle
    time each host span accounts for (averaged over chips)."""
    solves = [s for s in raw["spans"] if s[0] == WINDOW_SPAN]
    devices = raw["devices"]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    if solves:
        lo, hi = min(s[1] for s in solves), max(s[2] for s in solves)
    else:
        ends = [e for d in devices.values() for e in d["ops"]]
        lo, hi = min(e[1] for e in ends), max(e[2] for e in ends)
    busy, op_s, modules, gap_s = [], {}, {}, {}
    for dev in devices.values():
        ops = dev["ops"]
        iv = clip(np.array([(s, e) for _, s, e in ops]).reshape(-1, 2),
                  lo, hi)
        merged = union(iv)
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])))
        for name, s, e in ops:
            short = OP_NAME.match(name)
            name = " ".join(short.groups()) if short else name
            op_s[name] = op_s.get(name, 0.0) + (e - s)
        for name, s, e in dev["modules"]:
            if s >= lo and e <= hi:
                base = MODULE_NAME.match(name).group(1)
                n, t = modules.get(base, (0, 0.0))
                modules[base] = (n + 1, t + (e - s))
        edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        order = np.argsort((gaps[:, 0] + gaps[:, 1]) / 2)
        gaps = gaps[order]
        for name, (s, e) in zip(innermost((gaps[:, 0] + gaps[:, 1]) / 2,
                                          raw["spans"]), gaps):
            gap_s[name] = gap_s.get(name, 0.0) + (e - s)
    n = len(devices)
    return {"busy_s": sum(busy) / n, "window_s": hi - lo,
            "modules": modules,
            "top_ops": sorted(([k, v / n] for k, v in op_s.items()),
                              key=lambda kv: -kv[1])[:TOP],
            "top_gaps": sorted(([k, v / n] for k, v in gap_s.items()),
                               key=lambda kv: -kv[1])[:TOP]}


def reduce_dir(trace_dir: str, spans: Iterable[str]) -> dict:
    return reduce(load(xplane_path(trace_dir), spans))
