"""The trace reduction of ``trace_reduce``, extended by the program's own spans.

The runtime and the Jacobi app write ``jax.profiler.TraceAnnotation`` spans
named ``rt.*`` and ``jacobi.*`` on the threads that do the work (the caller,
the per-device workers, the progress lanes). They lie on the trace's host
plane, one line per thread, on the device planes' clock.

``reduce`` returns everything ``trace_reduce.reduce`` does, from the same
window, plus ``program_spans`` ({name: [count, seconds]} clipped to the
window), and puts the idle time down to the program where it can. Each idle
gap is cut where a program span starts or ends inside it, so that one long
gap (the end of one solve, the time between solves and the start of the
next) is not all put down to what holds its middle. Each piece then goes,
by its middle, to the threads that hold a working program span there, split
equally, each share to that thread's innermost span; where none does, the
same among the threads parked in an ``rt.wait.*`` span; where none is
either, to the harness's innermost span, ``solve`` or ``outside`` as
before. A trace without program spans reduces to exactly what
``trace_reduce`` gives.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import numpy as np

import trace_reduce

PREFIXES = ("rt.", "jacobi.")
WAIT = "rt.wait."


def program_events(path: str) -> List[Tuple[str, float, float, int]]:
    """The program's spans as (name, start, end, thread), the thread being
    the span's line on the host plane."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            out.extend((e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9, thread)
                       for e in line.events if e.name.startswith(PREFIXES))
    return out


def load(path: str, spans: Iterable[str]) -> dict:
    """``trace_reduce.load`` plus the program's spans under ``program``."""
    raw = trace_reduce.load(path, spans)
    raw["program"] = program_events(path)
    return raw


def window(raw: dict) -> Tuple[float, float]:
    """The window as ``trace_reduce.reduce`` takes it."""
    solves = [s for s in raw["spans"] if s[0] == trace_reduce.WINDOW_SPAN]
    if solves:
        return min(s[1] for s in solves), max(s[2] for s in solves)
    ops = [e for d in raw["devices"].values() for e in d["ops"]]
    return min(e[1] for e in ops), max(e[2] for e in ops)


def held(points: np.ndarray, program: list, waits: bool) -> List[list]:
    """Per thread, for each time in ``points``: the innermost working (or,
    with ``waits``, waiting) program span of that thread holding it, or
    None."""
    threads: Dict[int, list] = {}
    for name, s, e, thread in program:
        if name.startswith(WAIT) == waits:
            threads.setdefault(thread, []).append((name, s, e))
    return [[None if n == "outside" else n
             for n in trace_reduce.innermost(points, spans)]
            for spans in threads.values()]


def cut(gaps: np.ndarray, at: np.ndarray) -> np.ndarray:
    """``gaps`` (sorted, disjoint) cut at the times of ``at`` (sorted) that
    fall inside them."""
    if not len(at):
        return gaps
    lo = np.searchsorted(at, gaps[:, 0], side="right")
    hi = np.searchsorted(at, gaps[:, 1], side="left")
    pieces = []
    for (s, e), a, b in zip(gaps, lo, hi):
        edges = np.concatenate([[s], at[a:b], [e]])
        pieces.append(np.stack([edges[:-1], edges[1:]], axis=1))
    return np.concatenate(pieces) if pieces else gaps


def attribute(gaps: np.ndarray, raw: dict, gap_s: Dict[str, float]) -> None:
    """Adds to ``gap_s`` the idle seconds of ``gaps`` (sorted by their
    middles, cut at the program's span edges) per name they are put down
    to."""
    mids = (gaps[:, 0] + gaps[:, 1]) / 2
    program = raw.get("program", [])
    work, wait = held(mids, program, False), held(mids, program, True)
    harness = trace_reduce.innermost(mids, raw["spans"])
    for i, (s, e) in enumerate(gaps):
        names = ([h[i] for h in work if h[i]] or
                 [h[i] for h in wait if h[i]] or [harness[i]])
        share = (e - s) / len(names)
        for name in names:
            gap_s[name] = gap_s.get(name, 0.0) + share


def reduce(raw: dict) -> dict:
    out = trace_reduce.reduce(raw)
    lo, hi = window(raw)
    program = raw.get("program", [])
    spans: Dict[str, list] = {}
    for name, s, e, _ in program:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            n, t = spans.get(name, (0, 0.0))
            spans[name] = [n + 1, t + (e - s)]
    at = np.unique([t for _, s, e, _ in program for t in (s, e)])
    gap_s: Dict[str, float] = {}
    for dev in raw["devices"].values():
        iv = np.array([(s, e) for _, s, e in dev["ops"]]).reshape(-1, 2)
        merged = trace_reduce.union(trace_reduce.clip(iv, lo, hi))
        edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        gaps = gaps[np.argsort((gaps[:, 0] + gaps[:, 1]) / 2)]
        attribute(cut(gaps, at), raw, gap_s)
    n = len(raw["devices"])
    out["program_spans"] = spans
    out["top_gaps"] = sorted(([k, v / n] for k, v in gap_s.items()),
                             key=lambda kv: -kv[1])[:trace_reduce.TOP]
    return out


def reduce_dir(trace_dir: str, spans: Iterable[str]) -> dict:
    return reduce(load(trace_reduce.xplane_path(trace_dir), spans))


def span_total(ctx: dict, name: str) -> Tuple[int, float]:
    """(count, seconds) of a program span in a metric's ``ctx``; (0, 0.0)
    where the trace holds none or was reduced by ``trace_reduce`` alone."""
    n, seconds = (ctx["trace"] or {}).get("program_spans", {}).get(
        name, (0, 0.0))
    return n, seconds
