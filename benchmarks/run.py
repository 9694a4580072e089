"""Benchmark harness entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (stdout), mirroring:
  Fig. 8   tasking-framework optimization ladder (tasking_overhead)
  Fig. 10–12  ping-pong latency/bandwidth (pingpong)
plus a report of the runtime-ladder JSONs that
``benchmarks/run_dryrun_sweep.py --rt-ladder`` writes (run that first for
fresh data). All of it runs on the CPU; the chip benchmark is
``benchmarks/chip/``.

Each section runs in a child process of its own and this process never
imports JAX: a chip belongs to one process at a time, and a section that
takes it gives it back when its child exits.
"""
import json
import glob
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _section(title):
    print(f"# --- {title} ---", flush=True)


def main() -> None:
    sections = [
        ("fig8 tasking overhead ladder", "tasking_overhead"),
        ("fig10-12 pingpong", "pingpong"),
    ]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), REPO] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    failures = []
    for title, module in sections:
        _section(title)
        # keep the harness running: a failed section is reported, not fatal
        rc = subprocess.run(
            [sys.executable, "-c",
             f"from benchmarks import {module}; {module}.main()"],
            env=env, cwd=REPO).returncode
        if rc != 0:
            failures.append(title)
            print(f"SECTION_FAILED {title}: exit code {rc}", flush=True)

    _section("runtime ladder / residency report")
    for f in sorted(glob.glob(os.path.join(HERE, "results", "dryrun",
                                           "rt_ladder__*.json"))):
        base = os.path.basename(f)
        with open(f) as fh:
            d = json.load(fh)
        tag = base[len("rt_ladder__"):-len(".json")]
        if isinstance(d, dict) and "error" in d:
            print(f"rt_{tag},,ERROR")
        elif isinstance(d, dict) and "bytes_moved_ratio" in d:
            # SCHED-Locality: gravity-vs-baseline byte accounting
            print(f"rt_{tag},,"
                  f"baseline_moved={d['baseline']['bytes_moved']};"
                  f"gravity_moved={d['gravity']['bytes_moved']};"
                  f"ratio={d['bytes_moved_ratio']}")
        elif isinstance(d, list):
            for row in d:
                for key, val in row.items():
                    if not key.endswith("_stats") or not isinstance(val,
                                                                    dict):
                        continue
                    rung = key[:-len("_stats")]
                    pools = (f"stage={val.get('staging_hits')}/"
                             f"{val.get('staging_misses')};"
                             f"req={val.get('request_pool_hits')}/"
                             f"{val.get('request_pool_misses')}")
                    moved = sum(val.get(k) or 0 for k in
                                ("bytes_h2d", "bytes_d2h", "bytes_d2d"))
                    print(f"rt_{tag}_{rung}_{row['size']},,"
                          f"moved={moved};{pools};"
                          f"evict={val.get('evictions')}")
    if failures:
        print(f"# failed sections: {failures}", flush=True)
        sys.exit(1)
    print("# all benchmark sections completed", flush=True)


if __name__ == '__main__':
    main()
