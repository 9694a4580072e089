"""Sequential dry-run sweep: every (arch × shape) cell on the single-pod mesh
(+ optionally multi-pod), each in an isolated subprocess. Failures are
recorded and the sweep continues. Results land in benchmarks/results/dryrun/.

``--rt-ladder`` additionally sweeps the tasking-runtime optimization ladder
(benchmarks/tasking_overhead.py, paper Fig. 8) rung by rung — including the
transfer-engine rungs TF-Prefetch (RuntimeConfig.prefetch) and TF-D2D
(RuntimeConfig.d2d) — each rung in its own subprocess with a multi-device
CPU view so the D2D path is actually exercised.
"""
import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(REPO, "benchmarks", "results", "dryrun")


def cells():
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.configs import ARCH_IDS, get_config, shapes_for
    out = []
    for arch in ARCH_IDS:
        for shape in shapes_for(get_config(arch)):
            out.append((arch, shape.name))
    return out


def _run_subprocess_cell(tag, cmd, env, meta, timeout):
    """One sweep cell in an isolated subprocess: cached-JSON skip, error
    recording (``meta`` + the failure), and OK/FAIL/TIME reporting.
    Every cell is a CPU rehearsal, so the child never asks for the chip."""
    env = dict(env, JAX_PLATFORMS="cpu")
    out_path = os.path.join(OUT_DIR, tag + ".json")
    if os.path.exists(out_path):
        with open(out_path) as f:
            data = json.load(f)
        # success payloads are dicts without an "error" key or row lists;
        # failures are always dicts carrying "error"
        if "error" not in data:
            print(f"SKIP (cached) {tag}", flush=True)
            return
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, env=env, cwd=REPO)
        ok = proc.returncode == 0
        if not ok:
            with open(out_path, "w") as f:
                json.dump(dict(meta, error=(proc.stderr or "")[-3000:]),
                          f, indent=2)
        print(f"{'OK  ' if ok else 'FAIL'} {tag}  ({time.time()-t0:.0f}s)",
              flush=True)
    except subprocess.TimeoutExpired:
        with open(out_path, "w") as f:
            json.dump(dict(meta, error="timeout"), f)
        print(f"TIME {tag}", flush=True)


def run_cell(arch, shape, multi_pod, opt_level, timeout=3600):
    tag = f"{arch}__{shape}__{'pod2' if multi_pod else 'pod1'}__{opt_level}"
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--opt-level", opt_level, "--out",
           os.path.join(OUT_DIR, tag + ".json")]
    if multi_pod:
        cmd.append("--multi-pod")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    _run_subprocess_cell(tag, cmd, env,
                         {"arch": arch, "shape": shape,
                          "multi_pod": multi_pod, "opt_level": opt_level},
                         timeout)


def rt_ladder_rungs():
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    sys.path.insert(0, os.path.join(REPO, "src"))
    from tasking_overhead import EXTRA_RUNGS, LADDER
    return [name for name, _ in LADDER] + list(EXTRA_RUNGS)


def run_rt_rung(rung, devices=2, sizes="64,128", iters=30, timeout=1800):
    """One tasking-ladder rung in an isolated subprocess with ``devices``
    virtual CPU devices (so TF-D2D has a second device to transfer to)."""
    tag = f"rt_ladder__{rung}__dev{devices}"
    cmd = [sys.executable, os.path.join(REPO, "benchmarks",
                                        "tasking_overhead.py"),
           "--only", rung, "--sizes", sizes, "--iters", str(iters),
           "--json", os.path.join(OUT_DIR, tag + ".json")]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    _run_subprocess_cell(tag, cmd, env, {"rung": rung, "devices": devices},
                         timeout)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true",
                    help="also run the 2-pod mesh")
    ap.add_argument("--only-multi-pod", action="store_true")
    ap.add_argument("--opt-level", default="baseline")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--rt-ladder", action="store_true",
                    help="also sweep the tasking-runtime ladder "
                         "(TF-Baseline … TF-Prefetch, TF-D2D)")
    ap.add_argument("--rt-devices", type=int, default=2,
                    help="virtual devices for the runtime ladder")
    ap.add_argument("--rt-sizes", default="64,128",
                    help="matrix sizes for the runtime ladder (SCHED-"
                         "Locality uses the largest)")
    ap.add_argument("--rt-iters", type=int, default=30)
    ap.add_argument("--only-rt-ladder", action="store_true")
    args = ap.parse_args()
    os.makedirs(OUT_DIR, exist_ok=True)
    if args.rt_ladder or args.only_rt_ladder:
        for rung in rt_ladder_rungs():
            run_rt_rung(rung, devices=args.rt_devices,
                        sizes=args.rt_sizes, iters=args.rt_iters)
        if args.only_rt_ladder:
            print("sweep done", flush=True)
            return
    todo = cells()
    if args.arch:
        todo = [(a, s) for a, s in todo if a == args.arch]
    for arch, shape in todo:
        if not args.only_multi_pod:
            run_cell(arch, shape, False, args.opt_level)
        if args.multi_pod or args.only_multi_pod:
            run_cell(arch, shape, True, args.opt_level)
    print("sweep done", flush=True)


if __name__ == "__main__":
    main()
