"""Runs one cell traced, with the chip's idle time put down to the program's
own spans, and the per-layer metrics that read those spans.

    python3 benchmarks/chip/attribute.py --workload <config>.<traffic> \
        --seed N --seconds S

The run is ``run.py --trace 1``'s, with three additions: the trace is reduced
by ``program_trace`` (``breakdown.idle_gaps`` names ``rt.*`` and ``jacobi.*``
spans, and ``breakdown.program_spans`` gives each span's count and seconds),
the cell's counters take in the scheduler's ``ready_wait_s``, and the
metrics of ``PROGRAM_METRICS`` are read beside the cell's own. Prints the
result line last, with the trace's size and the process's wall time added.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

import harness  # noqa: E402
import program_trace  # noqa: E402
import trace_reduce  # noqa: E402

# per-layer metrics over the program's spans and counter, in the form of
# BENCHMARK.json's entries
PROGRAM_METRICS = [
    {"name": name, "unit": unit, "better": better, "source": source,
     "layer": layer, "moves": "iter_ms"}
    for name, unit, better, source, layer in (
        ("runtime_submit_us_per_task", "us", "lower", "program_span",
         "host task path"),
        ("launch_us_per_task", "us", "lower", "program_span",
         "host task path"),
        ("retire_us_per_task", "us", "lower", "program_span",
         "host task path"),
        ("ready_wait_us_per_task", "us", "lower", "program_counter",
         "scheduler"),
        ("h2d_gbps", "GB/s", "higher", "program_span", "transfer engine"),
        ("d2h_gbps", "GB/s", "higher", "program_span", "transfer engine"),
        ("app_copy_ms_per_iter", "ms", "lower", "program_span",
         "application"))]
COUNTER = "ready_wait_s"


def counting_ready_wait(load_module):
    """``harness.load_module`` that adds the scheduler's counter to the
    counters a cell's module (``drivers/<name>.py``) takes."""
    def load(path, name):
        mod = load_module(path, name)
        if name.startswith("bench_driver_") and COUNTER not in mod.COUNTERS:
            mod.COUNTERS = (*mod.COUNTERS, COUNTER)
        return mod
    return load


def run_cell(workload: str, seed: int, seconds: float, *, bench: dict,
             trace_dir: str, **kw) -> dict:
    """``harness.run_cell`` traced, with the additions above."""
    bench = dict(bench, per_layer=bench["per_layer"] + PROGRAM_METRICS)
    reduced = {}

    def reduce_dir(*args):
        reduced.update(program_trace.reduce_dir(*args))
        return reduced
    saved = harness.load_module, harness.trace_reduce
    harness.load_module = counting_ready_wait(saved[0])
    harness.trace_reduce = types.SimpleNamespace(reduce_dir=reduce_dir)
    try:
        result = harness.run_cell(workload, seed, seconds, True,
                                  started=STARTED, bench=bench,
                                  trace_dir=trace_dir, **kw)
    finally:
        harness.load_module, harness.trace_reduce = saved
    result["breakdown"]["program_spans"] = reduced["program_spans"]
    result["xplane_bytes"] = os.path.getsize(
        trace_reduce.xplane_path(trace_dir))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run  # the paths and the compile cache of a run
    bench = harness.load_json(run.ROOT / "BENCHMARK.json")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bench=bench, trace_dir=trace_dir)
    except harness.NoDevice as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["wall_s"] = time.perf_counter() - STARTED
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
