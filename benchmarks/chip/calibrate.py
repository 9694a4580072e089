"""The readings a cell's correctness limits are set from.

    python3 benchmarks/chip/calibrate.py --workload <config>.<traffic> \
        --seed N --seeds 3

In one process, on this machine's chips and at the cell's own size, for each
of ``--seeds`` seeds: the program's (lower) reading, one solve of the cell's
traffic compared with the reference as a run compares its sampled solve;
then the control's (upper), the reference computed in the precision below
the configuration's, read as a run reads the program. The cell's own runs
(``run.py``) add lower readings. Prints one JSON line per reading and a
summary line last.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (sets up paths and the compile cache)
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=3)
    args = ap.parse_args(argv)
    bench = harness.load_json(run.ROOT / "BENCHMARK.json")
    cell, config, traffic = harness.find_cell(args.workload, bench)
    harness.check_devices("tpu", cell["chips"])
    driver = harness.load_module(
        harness.HERE / "drivers" / f"{config['driver']}.py",
        f"bench_driver_{config['driver']}")
    lower, upper = {}, {}
    for seed in range(args.seed, args.seed + args.seeds):
        work = driver.Cell(config, traffic, seed)
        t = time.perf_counter()
        _, answer = work.solve(None)
        read = {k: v for k, (v, _) in work.check(answer).items()}
        del answer
        gc.collect()
        for k, v in read.items():
            lower[k] = max(lower.get(k, v), v)
        print(json.dumps({"program": seed, "seconds": time.perf_counter() - t,
                          "checked": read}), flush=True)
        t = time.perf_counter()
        read = work.control()
        del work
        gc.collect()
        for k, v in read.items():
            upper[k] = min(upper.get(k, v), v)
        print(json.dumps({"control": seed, "seconds": time.perf_counter() - t,
                          "checked": read}), flush=True)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper, "limits": config["limits"],
                      "seconds": time.perf_counter() - STARTED}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
