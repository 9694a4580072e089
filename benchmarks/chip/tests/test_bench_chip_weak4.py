"""The four-chip weak-scaling cell on four virtual CPU devices: the chunk
placement a traced run notes, the two per-layer metrics read from it and
from the transfer counters, and ``correct``.

The in-process suite fixes two CPU devices, so each run is a child process
with four."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

import bench_chip_util as util

WEAK = "jacobi3d_weak4_1024"
CHIPS = 4
SHAPE = [64, 16, 16]        # od 4 on four devices: an 8x2x1 grid of 8x8x16
ITERS = 2
PLACES = ["program", "slabs", "first"]

# One traced run of a small copy of the weak-scaling cell; prints the
# result line, the placement noted after each solve, and each face between
# two chunks with its bytes. ``place`` is "program" (the scheduler's own
# choice), "slabs" (each task on the device that owns its chunk in the
# plan, four chunks to a device) or "first" (every task on one device).
CHILD = """
import json, pathlib, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
import bench_chip_util as util
from bench_chip_util import harness
from repro.distributed.overdecomp import plan_decomposition

tmp, weak, shape, iters, place = (pathlib.Path(sys.argv[2]), sys.argv[3],
                                  json.loads(sys.argv[4]), int(sys.argv[5]),
                                  sys.argv[6])
here = util.copy_bench(tmp)
config = harness.load_json(here / "configs" / f"{weak}.json")
config["shape"] = shape
(here / "configs" / f"{util.SMALL}.json").write_text(json.dumps(config))
util.small_traffic(here, "od4_short", od=4, iters=iters)
cell = f"{util.SMALL}.od4_short"
bench = json.loads((tmp / "BENCHMARK.json").read_text())
bench["workloads"][-1].update(name=cell, traffic="od4_short")
for m in bench["per_layer"]:
    if f"{weak}.od4" in m.get("workloads", []):
        m["workloads"].append(cell)
(tmp / "BENCHMARK.json").write_text(json.dumps(bench))

# the CPU backend writes no device plane: stand in the recorded trace's
# reduction
harness.trace_reduce.reduce_dir = lambda *_: util.recorded_reduction()
noted = []
note = harness.Probe.note
def keep(self, key, value):
    if key == "placement":
        noted.append(value)
    note(self, key, value)
harness.Probe.note = keep
plan = plan_decomposition(shape, 4, 4)
if place != "program":
    from repro.core.scheduler import GravityScheduler
    def owner(self, task):
        devices = sorted(self.eligible(task))
        if place == "first":
            return devices[0]
        # the first argument of every run_tasked task is its chunk
        cid = int(task.args[0].obj.name[len("chunk"):])
        return devices[plan.owner_of(cid)]
    GravityScheduler._place = GravityScheduler._choose = owner

out = util.run(here, cell, trace=True, trace_dir=str(tmp / "trace"))
faces = [(c.cid, other, 4 * int(np.prod(c.shape)) // c.shape[int(tag[-1])])
         for c in plan.chunks for tag, other in plan.neighbors(c.cid).items()
         if other is not None]
print(json.dumps({"result": out, "placement": noted, "faces": faces,
                  "chunks": len(plan.chunks)}))
"""


def run_child(tmp_path, place: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={CHIPS}",
               PYTHONPATH=os.pathsep.join(
                   [str(util.ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CHILD),
         str(util.HERE / "tests"), str(tmp_path), WEAK, json.dumps(SHAPE),
         str(ITERS), place],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One child run for each placement, made when a test first asks."""
    made = {}

    def get(place: str) -> dict:
        if place not in made:
            made[place] = run_child(tmp_path_factory.mktemp(place), place)
        return made[place]
    return get


def device_of(placed: list) -> dict:
    """Chunk id -> index of the device whose copy the note names."""
    return {int(name[len("chunk"):]): d
            for d, names in enumerate(placed) for name in names}


def test_weak_cell_config_is_jacobi3d_1024_stacked_four_times():
    small = util.harness.load_json(util.HERE / "configs" /
                                   "jacobi3d_1024.json")
    weak = util.harness.load_json(util.HERE / "configs" / f"{WEAK}.json")
    assert weak["chips"] == CHIPS and weak["driver"] == small["driver"]
    assert weak["shape"] == [CHIPS * small["shape"][0]] + small["shape"][1:]
    assert weak["limits"] == small["limits"]


@pytest.mark.parametrize("place", PLACES)
def test_placement_note_holds_every_chunk_and_run_is_correct(runs, place):
    got = runs(place)
    assert len(got["placement"]) == got["result"]["attempted"] >= 1
    for placed in got["placement"]:
        assert len(placed) == CHIPS
        assert sorted(device_of(placed)) == list(range(got["chunks"]))
        assert sum(map(len, placed)) == got["chunks"] == 16
    assert got["result"]["correct"]
    assert got["result"]["checked"]["max_abs_gap"]["value"] == 0.0
    assert got["result"]["device"]["count"] == CHIPS


@pytest.mark.parametrize("place,counts,reads", [
    ("slabs", [4, 4, 4, 4], 1.0), ("first", [16, 0, 0, 0], 4.0)])
def test_chunk_imbalance_of_a_forced_placement(runs, place, counts, reads):
    got = runs(place)
    assert [len(names) for names in got["placement"][-1]] == counts
    assert got["result"]["metrics"]["chunk_imbalance"]["value"] == reads


def test_chunk_imbalance_of_the_programs_placement(runs):
    got = runs("program")
    counts = [len(names) for names in got["placement"][-1]]
    assert got["result"]["metrics"]["chunk_imbalance"]["value"] == \
        pytest.approx(max(counts) * CHIPS / sum(counts))


@pytest.mark.parametrize("counts,reads", [
    ([[4, 4, 4, 4]], 1.0), ([[16, 0, 0, 0]], 4.0),
    ([[4, 4, 4, 4], [8, 8, 0, 0]], 1.5), ([[0, 0, 0, 0]], None), (None, None)])
def test_chunk_imbalance_reader(counts, reads):
    reader = util.harness.load_module(
        util.HERE / "metrics" / "chunk_imbalance.py", "chunk_imbalance")
    notes = {} if counts is None else {"placement": [
        [[f"chunk{i}" for i in range(n)] for n in solve] for solve in counts]}
    assert reader.read({"notes": notes}) == reads


@pytest.mark.parametrize("place", ["slabs", "first"])
def test_d2d_bytes_are_the_faces_between_devices(runs, place):
    """Where no chunk moves, each face between chunks on two devices
    crosses once an iteration, and nothing else does."""
    got = runs(place)
    where = device_of(got["placement"][-1])
    crossing = sum(nbytes for a, b, nbytes in got["faces"]
                   if where[a] != where[b])
    # slabs of two chunk planes: three cuts, two faces each way across each
    assert crossing == {"slabs": 12, "first": 0}[place] * 4 * 8 * 16
    assert got["result"]["metrics"]["d2d_bytes_per_iter"]["value"] == \
        crossing
