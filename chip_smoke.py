"""Smoke run of the system's main paths on TPU chips, through the entry
points a user calls.

    python chip_smoke.py [--seed N]       # one chip
    python chip_smoke.py --chips 4        # the four-chip path only

One chip: the tasking runtime running Jacobi3D (512³ f32, over-decomposed
4 ways, 10 iterations) against the reference, the same run replayed as
compiled task graphs, the Pallas stencil compiled for the chip against the
jnp one, the distributed layer (two in-process ranks on the one chip), and
phi4-mini serving at its published width with random weights. Four chips: the
runtime over all four chips and the SPMD path on a four-chip mesh, both
against the one-chip reference.

Every phase prints one line with its result and the run stops at the first
failure with a non-zero exit. The last line is the JSON object
``{"ok": true, "device": {...}}`` naming the device as JAX reports it. With
no TPU present the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# the platform every phase must have run on
PLATFORM = "tpu"
# the tolerance tests/test_system.py holds run_tasked to
RTOL, ATOL = 1e-5, 1e-6
STAT_KEYS = ("tasks", "transfers_h2d", "transfers_d2h", "transfers_d2d",
             "bytes_h2d", "bytes_d2h", "bytes_d2d", "prefetch_hits",
             "graph_replays", "replayed_tasks")


def grid(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).random((n, n, n), dtype=np.float32)


def max_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.max(np.abs(got.astype(np.float64) - want)))


def chunk_placement(rt) -> dict:
    """Device id → number of Jacobi chunks resident there, after checking
    that each resident copy really lives on that device's chip."""
    placed = {}
    for dev in rt.devices:
        for obj in rt.residency.objects_on(dev.info.device_id):
            if not obj.name.startswith("chunk"):
                continue
            arr = obj.copies[dev.info.device_id]
            if arr.devices() != {dev.jax_device}:
                raise AssertionError(f"{obj.name} is recorded on {dev} but "
                                     f"lives on {arr.devices()}")
            placed[dev.info.device_id] = placed.get(dev.info.device_id, 0) + 1
    return placed


def phase_tasked(u0, want, iters, od, trace_graphs=False, n_devices=1):
    """run_tasked on a Runtime over ``n_devices`` chips, its capacity taken
    from the backend's memory_stats."""
    from repro.apps.jacobi3d import run_tasked
    from repro.core import Runtime, RuntimeConfig
    with Runtime(RuntimeConfig(trace_graphs=trace_graphs)) as rt:
        if len(rt.devices) != n_devices:
            raise AssertionError(f"runtime sees {len(rt.devices)} devices, "
                                 f"expected {n_devices}")
        t0 = time.perf_counter()
        got = run_tasked(u0, iters, rt, over_decomposition=od)
        seconds = time.perf_counter() - t0
        placed = chunk_placement(rt)
        stats = rt.stats()
        capacity = rt.devices[0].info.memory_capacity
        kinds = {d.jax_device.platform for d in rt.devices}
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    if kinds != {PLATFORM}:
        raise AssertionError(f"runtime devices are {kinds}, not {PLATFORM}")
    if trace_graphs and stats["graph_replays"] <= 0:
        raise AssertionError("no task graph was replayed")
    n_chunks = n_devices * od
    if sum(placed.values()) != n_chunks:
        raise AssertionError(f"{placed} holds {sum(placed.values())} of "
                             f"{n_chunks} chunks")
    info = {"seconds_with_compile": seconds, "max_abs_err": max_err(got, want),
            "capacity_bytes": capacity, "chunks_per_device": placed}
    info.update({k: stats[k] for k in STAT_KEYS})
    return info


def phase_pallas(u0, seed):
    """One sweep of the Pallas stencil, compiled for the chip, against the
    jnp stencil, with every face of the chunk non-zero."""
    from repro.apps.jacobi3d import stencil_jnp
    from repro.kernels import ops
    n0, n1, n2 = u0.shape
    rng = np.random.default_rng(seed)
    u = jnp.asarray(u0)
    faces = [jnp.asarray(rng.random(s, dtype=np.float32))
             for s in ((n1, n2), (n1, n2), (n0, n2), (n0, n2), (n0, n1),
                       (n0, n1))]
    t0 = time.perf_counter()
    compiled = jax.jit(ops.jacobi3d).lower(u, *faces).compile()
    compile_s = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("the stencil was not compiled as a TPU kernel")
    t0 = time.perf_counter()
    got = np.asarray(jax.block_until_ready(compiled(u, *faces)))
    run_s = time.perf_counter() - t0
    want = np.asarray(jax.jit(stencil_jnp)(u, *faces))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    return {"compile_seconds": compile_s, "first_run_seconds": run_s,
            "tpu_custom_call": True, "max_abs_err": max_err(got, want)}


def phase_cluster(u0, want, iters, residual_every):
    """run_cluster over two in-process ranks, unbilled wire."""
    from repro.apps.jacobi3d import run_cluster
    from repro.distributed import Cluster
    residuals = []
    with Cluster(2, ctrl_drain_per_s=0) as c:
        t0 = time.perf_counter()
        got = run_cluster(u0, iters, c, residual_every=residual_every,
                          residuals=residuals)
        seconds = time.perf_counter() - t0
        rank_stats = [r.stats for r in c.ranks]
        kinds = {d.jax_device.platform for r in c.ranks
                 for d in r.runtime.devices}
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    if kinds != {PLATFORM}:
        raise AssertionError(f"rank devices are {kinds}, not {PLATFORM}")
    if [it for it, _ in residuals] != list(
            range(residual_every, iters + 1, residual_every)):
        raise AssertionError(f"residual ticks {residuals}")

    def total(key):
        return sum(s[key] for s in rank_stats)
    return {"seconds_with_compile": seconds,
            "max_abs_err": max_err(got, want),
            "rendezvous": total("rendezvous"), "eager": total("eager"),
            "overlap_bytes": total("overlap_bytes"),
            "bytes_d2d": total("bytes_d2d"),
            "bytes_staged": total("bytes_staged"),
            "residuals": residuals}


def phase_serve(arch, batch, prompt_len, gen, smoke=False):
    """launch/serve.py's main: two generate calls from the same prompts;
    main raises if they disagree."""
    from repro.configs import get_config, get_smoke_config
    from repro.launch.serve import main as serve_main
    argv = ["--arch", arch, "--batch", str(batch),
            "--prompt-len", str(prompt_len), "--gen", str(gen)]
    vocab = (get_smoke_config(arch) if smoke else get_config(arch)).vocab
    out = np.asarray(serve_main(argv + ["--smoke"] * smoke))
    if out.shape != (batch, gen):
        raise AssertionError(f"generated {out.shape}, expected "
                             f"{(batch, gen)}")
    if out.min() < 0 or out.max() >= vocab:
        raise AssertionError(f"tokens outside [0, {vocab}): "
                             f"{out.min()}..{out.max()}")
    stats = jax.devices()[0].memory_stats() or {}
    return {"shape": list(out.shape), "repeat_equal": True,
            "token_range": [int(out.min()), int(out.max())],
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def phase_serve_reference(arch, gen=5):
    """On a small input: the serving engine's tokens (prefill, then decode
    from the grown cache) against greedy decoding by re-running prefill on
    the whole sequence, with the prompt exactly as long as a head is wide.
    Matmuls run at full f32 precision so that both sides pick the same
    tokens."""
    from repro.configs import get_smoke_config
    from repro.launch.serve import Engine
    from repro.models import build_smoke
    from repro.models.layers import unbox
    from repro.serve import make_prefill_step
    cfg = get_smoke_config(arch)
    m = build_smoke(cfg)
    b, s = 2, cfg.resolved_head_dim
    with jax.default_matmul_precision("highest"):
        params = unbox(m.init(jax.random.PRNGKey(0)))[0]
        tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                    cfg.vocab)
        got = np.asarray(Engine(m, params, b, s + gen).generate(tokens, gen))
        prefill = jax.jit(make_prefill_step(m))
        seq = tokens
        for _ in range(gen):
            nxt = prefill(params, {"tokens": seq},
                          m.init_cache(b, seq.shape[1]))[0]
            seq = jnp.concatenate([seq, nxt], axis=1)
    want = np.asarray(seq[:, s:])
    if not np.array_equal(got, want):
        raise AssertionError(f"engine gave {got.tolist()}, greedy prefill "
                             f"gave {want.tolist()}")
    return {"arch": arch, "prompt_len": s, "tokens": got.tolist()}


def run_phase(name, fn, *args, **kwargs):
    try:
        info = fn(*args, **kwargs)
    except Exception:
        traceback.print_exc()
        print(f"PHASE {name} FAIL", flush=True)
        sys.exit(1)
    print(f"PHASE {name} pass {json.dumps(info, default=str)}", flush=True)
    gc.collect()      # drop the phase's device buffers before the next one


def one_chip(seed: int) -> None:
    n, iters, od = 512, 10, 4
    u0 = grid(n, seed)
    want = reference("reference", u0, iters)
    run_phase("jacobi_tasked", phase_tasked, u0, want, iters, od)
    run_phase("jacobi_replay", phase_tasked, u0, want, iters, od,
              trace_graphs=True)
    run_phase("pallas_stencil", phase_pallas, u0, seed)
    del want
    n_c, iters_c = 256, 10
    u0 = grid(n_c, seed)
    want = reference("reference_cluster", u0, iters_c)
    run_phase("run_cluster", phase_cluster, u0, want, iters_c,
              residual_every=5)
    del u0, want
    gc.collect()
    run_phase("serve_reference_small", phase_serve_reference,
              "phi4_mini_3_8b")
    run_phase("serve_phi4_mini", phase_serve, "phi4_mini_3_8b", batch=4,
              prompt_len=128, gen=16)


def phase_tasked_spread(u0, want, iters, od, n_devices, trace_graphs=False):
    """phase_tasked over several chips: the chunks must land on every one
    of them and halos must cross between them device to device."""
    info = phase_tasked(u0, want, iters, od, trace_graphs=trace_graphs,
                        n_devices=n_devices)
    if sorted(info["chunks_per_device"]) != list(range(n_devices)):
        raise AssertionError(f"chunks not on all {n_devices} chips: "
                             f"{info['chunks_per_device']}")
    if info["transfers_d2d"] <= 0:
        raise AssertionError("no device-to-device transfer between chips")
    return info


def phase_spmd(u0, want, iters, n_devices):
    """run_spmd: shard_map over a mesh of ``n_devices`` chips."""
    from repro.apps.jacobi3d import run_spmd
    from repro.launch.mesh import make_mesh
    t0 = time.perf_counter()
    got = run_spmd(u0, iters, make_mesh((n_devices,), ("data",)))
    seconds = time.perf_counter() - t0
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    return {"seconds_with_compile": seconds, "max_abs_err": max_err(got, want)}


def phase_reference(u0, iters, out):
    """The single-array reference on the default (first) chip; the result
    goes to ``out["want"]``."""
    from repro.apps.jacobi3d import run_reference
    t0 = time.perf_counter()
    out["want"] = run_reference(u0, iters)
    return {"grid": list(u0.shape), "iters": iters,
            "seconds_with_compile": time.perf_counter() - t0}


def reference(name, u0, iters):
    out = {}
    run_phase(name, phase_reference, u0, iters, out)
    return out["want"]


def four_chips(seed: int) -> None:
    n, iters, od = 512, 10, 4
    u0 = grid(n, seed)
    want = reference("reference_one_chip", u0, iters)
    run_phase("jacobi_tasked_4chips", phase_tasked_spread, u0, want, iters,
              od, n_devices=4)
    run_phase("jacobi_spmd_4chips", phase_spmd, u0, want, iters,
              n_devices=4)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) != args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"devices: {devices}", flush=True)
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
