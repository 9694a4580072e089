"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

Prints one JSON line last on standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer ones), ``device`` and, last, ``checked``: each number compared
with the plain reference beside its limit, which also closes standard error.
Exits non-zero, printing no result, where the machine has no TPU or another
number of chips than the cell asks for.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402

# a fixed directory inside the checkout, unless JAX is given one: only the
# first run of a cell compiles, and every program (the per-solve kernels,
# which compile in well under a second, too) is found again
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), started=STARTED,
                                  bench=bench, trace_dir=trace_dir)
    except harness.NoDevice as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
