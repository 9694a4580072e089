"""Paper Fig. 13/15 — Jacobi3D strong/weak scaling and over-decomposition.

Strong/weak scaling run the SPMD production path on 1/2/4 virtual devices in
subprocesses (bulk_sync=True is the MPI+CUDA-style schedule; False lets XLA
overlap halo transfers with interior compute). Over-decomposition levels run
the PREMA-tasked path on the in-process runtime (Fig. 15).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time
from typing import Dict, List

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spmd_time(devices: int, domain, iters: int, bulk_sync: bool) -> float:
    code = f"""
        import numpy as np, time, jax
        from repro.apps.jacobi3d import make_spmd_step
        from jax.sharding import NamedSharding, PartitionSpec as PS
        import jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(({devices},), ('data',))
        step = make_spmd_step(mesh, 'data', bulk_sync={bulk_sync})
        rng = np.random.default_rng(0)
        u = jax.device_put(jnp.asarray(rng.random({tuple(domain)},
                           dtype=np.float32)), NamedSharding(mesh, PS('data')))
        u = step(u); u.block_until_ready()          # compile
        t0 = time.perf_counter()
        for _ in range({iters}):
            u = step(u)
        u.block_until_ready()
        print((time.perf_counter() - t0) / {iters})
    """
    env = dict(os.environ)
    # a CPU rehearsal on virtual devices: never the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return float(out.stdout.strip().splitlines()[-1])


def run_scaling(domain=(64, 64, 64), iters=10) -> List[Dict]:
    rows = []
    for devices in (1, 2, 4):
        t_sync = _spmd_time(devices, domain, iters, True)
        t_ovl = _spmd_time(devices, domain, iters, False)
        rows.append({"mode": "strong", "devices": devices,
                     "domain": list(domain),
                     "bulk_sync_ms": t_sync * 1e3,
                     "overlap_ms": t_ovl * 1e3,
                     "overlap_gain": t_sync / t_ovl})
        wdomain = (domain[0] * devices, domain[1], domain[2])
        t_sync = _spmd_time(devices, wdomain, iters, True)
        t_ovl = _spmd_time(devices, wdomain, iters, False)
        rows.append({"mode": "weak", "devices": devices,
                     "domain": list(wdomain),
                     "bulk_sync_ms": t_sync * 1e3,
                     "overlap_ms": t_ovl * 1e3,
                     "overlap_gain": t_sync / t_ovl})
    return rows


def run_overdecomposition(domain=(32, 32, 32), iters=4) -> List[Dict]:
    from repro.core import Runtime, RuntimeConfig
    from repro.apps.jacobi3d import run_tasked
    rng = np.random.default_rng(0)
    u0 = rng.random(domain).astype(np.float32)
    rows = []
    for od in (1, 2, 4):
        with Runtime(RuntimeConfig(memory_capacity=1 << 30)) as rt:
            run_tasked(u0, 1, rt, over_decomposition=od)   # warm compile
            t0 = time.perf_counter()
            run_tasked(u0, iters, rt, over_decomposition=od)
            dt = (time.perf_counter() - t0) / iters
        rows.append({"od": od, "ms_per_iter": dt * 1e3})
    return rows


def run_transfer_engine(domain=(32, 32, 32), iters=4, od=4) -> List[Dict]:
    """Transfer-engine ablation on the tasked Jacobi pipeline: the paper's
    §4.1.3 overlap (argument prefetch) and §3.2.3 direct D2D path, on vs
    off, on the over-decomposed PREMA schedule."""
    from repro.core import Runtime, RuntimeConfig
    from repro.apps.jacobi3d import run_tasked
    rng = np.random.default_rng(0)
    u0 = rng.random(domain).astype(np.float32)
    rows = []
    for label, kw in (("off", dict(d2d=False, prefetch=False)),
                      ("prefetch", dict(d2d=False, prefetch=True)),
                      ("prefetch_d2d", dict(d2d=True, prefetch=True))):
        with Runtime(RuntimeConfig(memory_capacity=1 << 30, **kw)) as rt:
            run_tasked(u0, 1, rt, over_decomposition=od)   # warm compile
            t0 = time.perf_counter()
            run_tasked(u0, iters, rt, over_decomposition=od)
            dt = (time.perf_counter() - t0) / iters
            stats = rt.stats()
        rows.append({"cfg": label, "ms_per_iter": dt * 1e3,
                     # staged = claimed-early copies, hit or stalled
                     "prefetch_staged": stats["prefetch_hits"]
                     + stats["prefetch_stalls"],
                     "transfers_d2d": stats["transfers_d2d"]})
    return rows


def main():
    print("name,us_per_call,derived")
    for r in run_scaling():
        print(f"fig13_{r['mode']}_d{r['devices']}_sync,"
              f"{r['bulk_sync_ms'] * 1e3:.0f},")
        print(f"fig13_{r['mode']}_d{r['devices']}_overlap,"
              f"{r['overlap_ms'] * 1e3:.0f},gain_x{r['overlap_gain']:.2f}")
    for r in run_overdecomposition():
        print(f"fig15_od{r['od']},{r['ms_per_iter'] * 1e3:.0f},")
    for r in run_transfer_engine():
        print(f"xfer_{r['cfg']},{r['ms_per_iter'] * 1e3:.0f},"
              f"pf{r['prefetch_staged']}_d2d{r['transfers_d2d']}")


if __name__ == "__main__":
    main()
