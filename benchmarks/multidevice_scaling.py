"""Paper Fig. 9 — multi-device scaling with/without dedicated device threads.

Throughput of independent matmul tasks over 1/2/4 virtual devices, dedicated
threads on vs off. NOTE: this container exposes ONE physical core, so
speedups cannot exceed 1 for compute-bound work; what this benchmark
demonstrates on CPU is (a) work actually spreads across devices, (b) the
dedicated-thread dispatch path's overhead behaviour. On a real multi-chip
host the same harness exhibits the paper's near-linear scaling.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from typing import Dict, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _throughput(devices: int, dedicated: bool, n: int = 64,
                tasks: int = 120) -> Dict:
    code = f"""
        import numpy as np, time, json, collections
        from repro.core import Runtime, RuntimeConfig
        cfg = RuntimeConfig(scheduler='least_loaded',
                            dedicated_threads={dedicated},
                            memory_capacity=1 << 30)
        with Runtime(cfg) as rt:
            objs = [rt.hetero_object(np.random.rand({n}, {n}).astype(
                np.float32)) for _ in range(16)]
            outs = [rt.hetero_object(shape=({n}, {n}), dtype=np.float32)
                    for _ in range(16)]
            k = lambda a, o: (a @ a.T).astype(a.dtype)
            for i in range(16):
                rt.run(k, [(objs[i], 'r'), (outs[i], 'w')])
            rt.barrier()
            t0 = time.perf_counter()
            ts = []
            for i in range({tasks}):
                ts.append(rt.run(k, [(objs[i % 16], 'r'),
                                     (outs[i % 16], 'w')]))
            rt.barrier(timeout=600)
            dt = time.perf_counter() - t0
            used = collections.Counter(t.chosen_device for t in ts)
            print(json.dumps({{'tps': {tasks} / dt,
                               'devices_used': len(used)}}))
    """
    env = dict(os.environ)
    # a CPU rehearsal on virtual devices: never the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def run() -> List[Dict]:
    rows = []
    base = None
    for devices in (1, 2, 4):
        for dedicated in (False, True):
            r = _throughput(devices, dedicated)
            row = {"devices": devices, "dedicated_threads": dedicated,
                   "tasks_per_s": round(r["tps"], 1),
                   "devices_used": r["devices_used"]}
            if devices == 1 and dedicated:
                base = r["tps"]
            rows.append(row)
    for row in rows:
        row["speedup_vs_1dev"] = round(row["tasks_per_s"] / base, 2) \
            if base else None
    return rows


def main():
    print("name,us_per_call,derived")
    for r in run():
        tag = f"d{r['devices']}_{'ded' if r['dedicated_threads'] else 'nod'}"
        print(f"fig9_{tag},{1e6 / r['tasks_per_s']:.0f},"
              f"x{r['speedup_vs_1dev']};used{r['devices_used']}")


if __name__ == "__main__":
    main()
