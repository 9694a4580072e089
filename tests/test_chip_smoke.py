"""chip_smoke.py refuses to run without a TPU, and its phases pass on the
CPU at a tiny size: the chip run is the same code at full size."""
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEVICES = 2          # conftest.py's CPU view


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mod, "PLATFORM", "cpu")
        yield mod


@pytest.fixture(scope="module")
def grid(smoke):
    from repro.apps.jacobi3d import run_reference
    u0 = smoke.grid(16, seed=3)
    return u0, run_reference(u0, 6)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("trace_graphs", [False, True])
def test_tasked_phase_spreads_over_devices(smoke, grid, trace_graphs):
    u0, want = grid
    info = smoke.phase_tasked_spread(u0, want, 6, od=2, n_devices=N_DEVICES,
                                     trace_graphs=trace_graphs)
    assert sum(info["chunks_per_device"].values()) == 2 * N_DEVICES
    assert (info["graph_replays"] > 0) == trace_graphs


def test_spmd_phase(smoke, grid):
    u0, want = grid
    info = smoke.phase_spmd(u0, want, 6, n_devices=N_DEVICES)
    assert info["max_abs_err"] <= smoke.ATOL + smoke.RTOL


def test_cluster_phase(smoke, grid):
    u0, want = grid
    info = smoke.phase_cluster(u0, want, 6, residual_every=3)
    assert [it for it, _ in info["residuals"]] == [3, 6]


def test_serve_phase(smoke):
    info = smoke.phase_serve("phi4_mini_3_8b", batch=2, prompt_len=16,
                             gen=4, smoke=True)
    assert info["shape"] == [2, 4] and info["repeat_equal"]


@pytest.mark.parametrize("arch", ["phi4_mini_3_8b", "recurrentgemma_9b"])
def test_serve_reference_phase(smoke, arch):
    info = smoke.phase_serve_reference(arch)
    assert len(info["tokens"]) == 2 and len(info["tokens"][0]) == 5
