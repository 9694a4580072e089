"""The chip benchmark's harness on the CPU: cells, mixes and metrics found by
name, the device and peak checks, and the comparison that decides
``correct`` failing for each fault a Jacobi cell can have."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import bench_chip_util as util
from bench_chip_util import harness

import reference


def test_every_cell_finds_its_files():
    bench = harness.load_json(util.ROOT / "BENCHMARK.json")
    for cell in bench["workloads"]:
        _, config, traffic = harness.find_cell(cell["name"], bench)
        assert config["chips"] == cell["chips"]
        assert (util.HERE / "drivers" / f"{config['driver']}.py").exists()
        assert traffic["iters_per_solve"] > 0
    for m in bench["per_layer"]:
        assert (util.HERE / "metrics" / f"{m['name']}.py").exists()


def test_new_config_traffic_and_metric_need_no_edit(tmp_path, monkeypatch):
    """A configuration, a traffic mix and a per-layer metric added as new
    files are run without a change to any file that was there."""
    here = util.copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    util.small_traffic(here, "od8_short", od=8, iters=2)
    (here / "metrics" / "tasks_per_iter.py").write_text(
        "def read(ctx):\n"
        "    return ctx['counters']['tasks'] / ctx['iterations']\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"][-1].update(name=f"{util.SMALL}.od8_short",
                                  traffic="od8_short")
    bench["per_layer"].append({
        "name": "tasks_per_iter", "unit": "1", "better": "lower",
        "source": "program_counter", "layer": "host task path",
        "moves": "iter_ms", "workloads": [f"{util.SMALL}.od8_short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # the CPU backend writes no device plane: stand in the recorded trace's
    # reduction
    monkeypatch.setattr(harness.trace_reduce, "reduce_dir",
                        lambda *_: util.recorded_reduction())
    out = util.run(here, f"{util.SMALL}.od8_short", trace=True,
                   trace_dir=str(tmp_path / "trace"))
    n = 8 * len(jax.devices())
    # faces between neighbours plus one update per chunk, every iteration
    assert out["metrics"]["tasks_per_iter"]["value"] > n
    assert out["correct"]
    assert {p: p.read_bytes() for p in before} == before


def test_untraced_run_reports_the_end_to_end_metrics(tmp_path):
    here = util.copy_bench(tmp_path)
    out = util.run(here, f"{util.SMALL}.od4")
    assert set(out["metrics"]) == {"iter_ms", "setup_s"}
    assert out["metrics"]["iter_ms"]["value"] > 0
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["correct"]
    assert out["checked"]["max_abs_gap"]["value"] == 0.0
    assert list(out)[-1] == "checked"
    assert out["device"]["count"] == len(jax.devices())


def test_same_seed_same_grid_any_seed_size():
    import grids
    a = grids.uniform((8, 4, 4), 2 ** 33 + 5)
    b = grids.uniform((8, 4, 4), 2 ** 33 + 5, devices=jax.devices()[:1])
    c = grids.uniform((8, 4, 4), 2 ** 33 + 6)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.float32 and 0 <= a.min() and a.max() < 1


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in"):
        harness.lookup_peaks("TPU v99")
    assert harness.lookup_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_tpu_refused():
    with pytest.raises(harness.NoDevice):
        harness.check_devices("tpu", 1)
    with pytest.raises(harness.NoDevice):
        harness.check_devices("cpu", len(jax.devices()) + 1)


def test_command_without_tpu_prints_no_result():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "jacobi3d_1024.od4", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=util.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "refused" in p.stderr


# faults planted under the timed path; each must turn `correct` false


def _unchanged_state(monkeypatch):
    import repro.apps.jacobi3d as app
    monkeypatch.setattr(app, "stencil_update", lambda u, *faces: u)


def _no_halo_exchange(monkeypatch):
    from repro.core import Runtime
    run = Runtime.run

    def skip_faces(self, kernel, args, device_type=None, name=""):
        if name.startswith("halo"):
            return None
        return run(self, kernel, args, device_type, name)
    monkeypatch.setattr(Runtime, "run", skip_faces)


def _altered_answer(run_tasked):
    def altered(*args, **kwargs):
        out = run_tasked(*args, **kwargs)
        out[3, 5, 7] += 1e-2
        return out
    return altered


def _in_driver(monkeypatch, replace):
    """Swap the driver's ``run_tasked`` for ``replace(run_tasked)``. The
    driver binds it when the harness loads the driver, so the swap is made
    in the loaded module."""
    load = harness.load_module

    def load_and_swap(path, name):
        mod = load(path, name)
        if name.startswith("bench_driver_"):
            monkeypatch.setattr(mod, "run_tasked", replace(mod.run_tasked))
        return mod
    monkeypatch.setattr(harness, "load_module", load_and_swap)


def _control(_run_tasked):
    """The control: the plain reference computed in bfloat16."""
    return lambda u0, iters, rt, over_decomposition: reference.run(
        u0, iters, jax.numpy.bfloat16)


@pytest.mark.parametrize("fault", ["unchanged_state", "no_halo_exchange",
                                   "altered_answer", "control"])
def test_fault_under_the_timed_path_is_not_correct(tmp_path, monkeypatch,
                                                    fault):
    here = util.copy_bench(tmp_path)
    if fault in ("altered_answer", "control"):
        _in_driver(monkeypatch, {"altered_answer": _altered_answer,
                                 "control": _control}[fault])
    else:
        {"unchanged_state": _unchanged_state,
         "no_halo_exchange": _no_halo_exchange}[fault](monkeypatch)
    out = util.run(here, f"{util.SMALL}.od4")
    gap = out["checked"]["max_abs_gap"]
    assert not out["correct"] and gap["value"] > gap["limit"], gap


def test_control_fails_the_limit():
    """The reference in bfloat16 in the program's place (the control) reads
    above the float32 cells' limit."""
    config = harness.load_json(util.HERE / "configs" / "jacobi3d_1024.json")
    u0 = np.random.default_rng(3).random((32, 32, 32), dtype=np.float32)
    gap = reference.control_gap(u0, 20, jax.numpy.bfloat16)
    assert gap > config["limits"]["max_abs_gap"]
