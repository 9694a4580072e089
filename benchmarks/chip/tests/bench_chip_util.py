"""Helpers for the chip benchmark's CPU tests: a copy of the harness with a
small cell of its own, run on the CPU devices."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

import jax

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import harness  # noqa: E402

SMALL = "jacobi3d_small"


def copy_bench(tmp: pathlib.Path, traffic: str = "od4") -> pathlib.Path:
    """A copy of the benchmark under ``tmp`` with one more cell,
    ``jacobi3d_small.<traffic>``: a 16^3 grid on however many CPU devices
    this process has, held to the limits of ``jacobi3d_1024``. Returns the
    copy's harness directory."""
    here = tmp / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "testdata"))
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    config = harness.load_json(HERE / "configs" / "jacobi3d_1024.json")
    config.update(shape=[16, 16, 16], chips=len(jax.devices()))
    (here / "configs" / f"{SMALL}.json").write_text(json.dumps(config))
    bench["configs"].append({"name": SMALL, "source": "test",
                             "file": f"benchmarks/chip/configs/{SMALL}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{SMALL}.{traffic}", "config": SMALL,
                               "traffic": traffic,
                               "chips": len(jax.devices()), "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    # the CPU has no published peaks; the copy's table gets stand-ins so
    # that the metrics dividing by a peak can be exercised
    peaks = harness.load_json(here / "peaks.json")
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "source": "stand-in for the CPU tests"}
    (here / "peaks.json").write_text(json.dumps(peaks))
    return here


def small_traffic(here: pathlib.Path, name: str, od: int = 2,
                  iters: int = 3) -> None:
    (here / "traffic" / f"{name}.json").write_text(json.dumps(
        {"over_decomposition": od, "iters_per_solve": iters, "why": "test"}))


def run(here: pathlib.Path, workload: str, seed: int = 2 ** 31 + 7,
        trace: bool = False, **kw) -> dict:
    """One run of ``workload`` from the copy at ``here``, on the CPU."""
    bench = harness.load_json(here.parents[1] / "BENCHMARK.json")
    return harness.run_cell(workload, seed, 0.0, trace,
                            started=time.perf_counter(), bench=bench,
                            here=here, platform="cpu", **kw)


# a trace recorded on one v5e chip: run_tasked over a 64^3 grid, od 4,
# two iterations, with the harness's spans (see test_bench_chip_trace.py)
RECORDED = HERE / "testdata" / "small_od4.xplane.pb"


def recorded_reduction() -> dict:
    import trace_reduce
    return trace_reduce.reduce(trace_reduce.load(str(RECORDED),
                                                 harness.SPANS))
