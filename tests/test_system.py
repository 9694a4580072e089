"""End-to-end behaviour tests: the full system wired together — runtime +
distributed layer + training driver + serving engine + dry-run machinery."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_driver_end_to_end(tmp_path):
    """launch.train: fresh run, then resume from checkpoint — the production
    driver path."""
    from repro.launch.train import main as train_main
    args = ["--arch", "yi-9b", "--smoke", "--steps", "12",
            "--global-batch", "4", "--seq-len", "32", "--ckpt-every", "6",
            "--checkpoint-dir", str(tmp_path), "--log-every", "6"]
    state = train_main(args)
    assert int(state.opt.step) == 12
    # resume: driver must pick up from the last committed checkpoint
    state2 = train_main(args + ["--steps", "18"])
    assert int(state2.opt.step) == 18


def test_serve_engine_end_to_end():
    from repro.launch.serve import main as serve_main
    out = serve_main(["--arch", "recurrentgemma-9b", "--smoke",
                      "--batch", "2", "--prompt-len", "16", "--gen", "4"])
    assert out.shape == (2, 4)
    assert not bool(jnp.any(out < 0))


def test_prema_jacobi_pipeline_with_runtime():
    """The paper's proxy pipeline: over-decomposed Jacobi through the tasking
    runtime matches the reference and actually overlaps (more tasks than
    chunks·iters implies halo+update pipelines ran)."""
    from repro.apps.jacobi3d import run_reference, run_tasked
    from repro.core import Runtime, RuntimeConfig
    rng = np.random.default_rng(2)
    u0 = rng.random((8, 8, 8)).astype(np.float32)
    want = run_reference(u0, 2)
    with Runtime(RuntimeConfig(memory_capacity=1 << 26)) as rt:
        got = run_tasked(u0, 2, rt, over_decomposition=2)
        stats = rt.stats()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert stats["tasks"] > 2 * 2  # halo tasks + update tasks per iteration


def test_dryrun_machinery_smoke():
    """lower_cell on the production mesh in a subprocess (512 virtual
    devices) — the smallest cell, end to end through the real dry-run path."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=512'\n"
        "from repro.launch.dryrun import lower_cell\n"
        "r = lower_cell('olmoe_1b_7b', 'decode_32k')\n"
        "assert r['chips'] == 256, r['chips']\n"
        "assert r.get('flops_per_device', 0) > 0\n"
        "assert r.get('hlo_lines', 0) > 0, r\n"
        "print('dryrun ok', r['hlo_lines'])\n"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "dryrun ok" in out.stdout


def test_dryrun_results_all_pass():
    """If the sweep has been run, every produced cell must be error-free on
    both meshes (the multi-pod deliverable)."""
    import glob
    files = glob.glob(os.path.join(REPO, "benchmarks", "results", "dryrun",
                                   "*__baseline.json"))
    if not files:
        pytest.skip("dry-run sweep not yet executed")
    bad = []
    for f in files:
        d = json.load(open(f))
        if "error" in d:
            bad.append(os.path.basename(f))
    assert not bad, f"failed dry-run cells: {bad}"
