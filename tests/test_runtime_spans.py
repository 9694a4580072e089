"""The runtime's and Jacobi's profiler spans, and the scheduler's queue-time
counter: a traced ``run_tasked`` writes the spans its layers promise, on the
threads that do the work, and ``stats()`` sums the time tasks sat READY."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.apps.jacobi3d import run_reference, run_tasked
from repro.core import Runtime, RuntimeConfig

PREFIXES = ("rt.", "jacobi.")


def program_spans(trace_dir):
    """{name: [(start_s, end_s, host line), ...]} of the program's spans."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, line_no))
    return spans


@pytest.fixture(scope="module")
def traced_solve(tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    u0 = np.random.default_rng(0).random((16, 16, 16), dtype=np.float32)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        with jax.profiler.trace(trace_dir, profiler_options=options):
            out = run_tasked(u0, 3, rt, over_decomposition=2)
        stats = rt.stats()
        n_chunks = 2 * len(rt.devices)
    return {"u0": u0, "out": out, "stats": stats, "n_chunks": n_chunks,
            "spans": program_spans(trace_dir)}


def test_traced_solve_is_still_right(traced_solve):
    ref = np.asarray(run_reference(traced_solve["u0"], 3))
    np.testing.assert_allclose(traced_solve["out"], ref, atol=1e-5)


@pytest.mark.parametrize("name", ["rt.submit", "rt.launch", "rt.retire",
                                  "rt.dispatch"])
def test_one_span_per_task(traced_solve, name):
    assert len(traced_solve["spans"][name]) == traced_solve["stats"]["tasks"]


@pytest.mark.parametrize("name", ["rt.d2h", "rt.get"])
def test_one_span_per_chunk(traced_solve, name):
    assert len(traced_solve["spans"][name]) == traced_solve["n_chunks"]


def test_one_span_per_device_to_device_copy(traced_solve):
    n = traced_solve["stats"]["transfers_d2d"]
    assert n > 0 and len(traced_solve["spans"]["rt.d2d"]) == n


def test_one_span_per_replayed_window(tmp_path):
    u0 = np.random.default_rng(2).random((16, 16, 16), dtype=np.float32)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    cfg = RuntimeConfig(memory_capacity=1 << 28, trace_graphs=True,
                        replay_after=2)
    with Runtime(cfg) as rt:
        with jax.profiler.trace(str(tmp_path), profiler_options=options):
            run_tasked(u0, 6, rt, over_decomposition=2)
        replays = rt.stats()["graph_replays"]
    assert replays > 0
    assert len(program_spans(str(tmp_path))["rt.replay"]) == replays


def test_one_split_per_solve(traced_solve):
    assert len(traced_solve["spans"]["jacobi.split"]) == 1


def test_no_separate_assembly_copy(traced_solve):
    assert "jacobi.assemble" not in traced_solve["spans"]


def test_every_chunk_downloads_into_the_output(traced_solve):
    assert traced_solve["stats"]["d2h_direct"] == traced_solve["n_chunks"]


@pytest.mark.parametrize("child, parent", [("rt.dispatch", "rt.launch"),
                                           ("rt.d2h", "rt.get")])
def test_child_span_nests_in_its_parent_on_one_thread(traced_solve, child,
                                                      parent):
    outer = traced_solve["spans"][parent]
    for s, e, line in traced_solve["spans"][child]:
        assert any(ps <= s and e <= pe and pl == line
                   for ps, pe, pl in outer)


def test_submits_run_on_the_caller_and_launches_on_workers(traced_solve):
    spans = traced_solve["spans"]
    (split_line,) = {ln for _, _, ln in spans["jacobi.split"]}
    assert {ln for _, _, ln in spans["rt.submit"]} == {split_line}
    assert split_line not in {ln for _, _, ln in spans["rt.launch"]}


def test_ready_wait_counts_queue_time(traced_solve):
    assert traced_solve["stats"]["ready_wait_s"] > 0.0


def test_ready_wait_is_zero_before_any_task():
    with Runtime(RuntimeConfig(memory_capacity=1 << 28)) as rt:
        assert rt.stats()["ready_wait_s"] == 0.0
        x = rt.hetero_object(np.ones((4, 4), np.float32))
        rt.run(lambda v: v + 1, [(x, "rw")])
        rt.barrier()
        assert rt.stats()["ready_wait_s"] >= 0.0

