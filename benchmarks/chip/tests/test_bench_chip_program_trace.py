"""The program's spans in the trace reduction: idle gaps put down to the
runtime's and the app's own spans, their totals, and the per-layer metrics
that read them, on made-up events and on small traces recorded on a v5e."""
from __future__ import annotations

import pytest

import bench_chip_util as util
from bench_chip_util import harness

import program_trace
import trace_reduce

# a trace recorded on one v5e chip as util.RECORDED was, with the program's
# own spans besides the harness's
RECORDED_SPANS = util.HERE / "testdata" / "small_od4_spans.xplane.pb"
READERS = ("runtime_submit_us_per_task", "launch_us_per_task",
           "retire_us_per_task", "ready_wait_us_per_task", "h2d_gbps",
           "d2h_gbps", "app_copy_ms_per_iter")


def made_up() -> dict:
    """One chip busy 1-2 and 6-7 of a 0-10 window: idle 0-1, 2-6 and 7-10.
    Thread 0 is the caller, 1 a worker, 2 another worker."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [("fusion", 1.0, 2.0), ("fusion", 6.0, 7.0)],
            "modules": []}},
        "spans": [("solve", 0.0, 10.0), ("barrier", 3.0, 9.0)],
        "program": [
            ("rt.submit", 0.0, 1.0, 0),
            ("rt.launch", -0.5, 1.5, 1), ("rt.dispatch", 0.0, 1.0, 1),
            ("rt.wait.inflight", -1.0, 1.5, 2),
            ("rt.wait.idle", 2.0, 4.0, 1),
            ("jacobi.assemble", 7.0, 8.0, 0),
            ("rt.retire", 9.5, 10.5, 1),
            ("rt.h2d", 11.0, 12.0, 2)]}


def gaps_of(raw: dict) -> dict:
    return dict(program_trace.reduce(raw)["top_gaps"])


def test_gap_split_equally_among_the_threads_at_work():
    gaps = gaps_of(made_up())
    # 0-1: the caller's submit and the worker's dispatch (its innermost
    # span), half each; the parked worker gets nothing
    assert gaps["rt.submit"] == pytest.approx(0.5)
    assert gaps["rt.dispatch"] == pytest.approx(0.5)
    assert "rt.launch" not in gaps and "rt.wait.inflight" not in gaps


def test_waits_before_harness_spans():
    gaps = gaps_of(made_up())
    # 2-6 is cut where the park ends: 2-4 parked, 4-6 the barrier alone
    assert gaps["rt.wait.idle"] == pytest.approx(2.0)
    # 7-10 is cut at 8 and 9.5: the barrier holds 8-9.5 alone
    assert gaps["barrier"] == pytest.approx(2.0 + 1.5)


def test_long_gap_cut_at_program_span_edges():
    gaps = gaps_of(made_up())
    # the middle of 7-10 (8.5) lies in the barrier alone, but 7-8 is the
    # assembly's and 9.5-10 the retirement's
    assert gaps["jacobi.assemble"] == pytest.approx(1.0)
    assert gaps["rt.retire"] == pytest.approx(0.5)
    assert "solve" not in gaps and "outside" not in gaps


def test_gap_totals_still_sum_to_idle_time():
    r = program_trace.reduce(made_up())
    assert r["window_s"] == 10.0 and r["busy_s"] == 2.0
    assert sum(v for _, v in r["top_gaps"]) == pytest.approx(8.0)


def test_program_span_totals_clipped_to_the_window():
    spans = program_trace.reduce(made_up())["program_spans"]
    assert spans["rt.launch"] == [1, pytest.approx(1.5)]
    assert spans["rt.retire"] == [1, pytest.approx(0.5)]
    assert "rt.h2d" not in spans


def test_without_waits_the_harness_span_takes_the_gap():
    raw = made_up()
    raw["program"] = [p for p in raw["program"]
                      if not p[0].startswith(program_trace.WAIT)]
    gaps = gaps_of(raw)
    assert gaps["barrier"] == pytest.approx(4.0 + 1.5)
    assert trace_reduce.WINDOW_SPAN not in gaps


def test_recorded_trace_without_program_spans_reduces_as_before():
    """The trace recorded before the program had spans: every number of
    ``trace_reduce`` comes out the same, and no program span is found."""
    old = util.recorded_reduction()
    new = program_trace.reduce(program_trace.load(str(util.RECORDED),
                                                  harness.SPANS))
    assert new.pop("program_spans") == {}
    assert new == old


def parent_ctx() -> dict:
    """What a metric sees in a run of a program without spans or the
    scheduler's counter."""
    return {"iterations": 200, "window_s": 60.0,
            "counters": {"tasks": 2400, "replayed_tasks": 0,
                         "bytes_h2d": 1 << 32, "bytes_d2h": 1 << 32,
                         "bytes_d2d": 0},
            "notes": {}, "spans": {}, "trace": util.recorded_reduction(),
            "peaks": {}, "chips": 1, "work": {}}


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_a_program_without_spans(name):
    reader = harness.load_module(util.HERE / "metrics" / f"{name}.py", name)
    assert reader.read(parent_ctx()) is None


def test_readers_on_program_spans():
    ctx = parent_ctx()
    ctx["counters"]["ready_wait_s"] = 0.6
    ctx["trace"] = dict(ctx["trace"], program_spans={
        "rt.submit": [2400, 0.06], "rt.launch": [2400, 1.2],
        "rt.retire": [2400, 0.024], "rt.h2d": [8, 4.0],
        "rt.d2h": [8, 8.0], "jacobi.split": [2, 4.0],
        "jacobi.assemble": [2, 6.0]})
    read = {name: harness.load_module(
        util.HERE / "metrics" / f"{name}.py", name).read(ctx)
        for name in READERS}
    assert read == pytest.approx({
        "runtime_submit_us_per_task": 25.0, "launch_us_per_task": 500.0,
        "retire_us_per_task": 10.0, "ready_wait_us_per_task": 250.0,
        "h2d_gbps": (1 << 32) / 4e9, "d2h_gbps": (1 << 32) / 8e9,
        "app_copy_ms_per_iter": 50.0})


def test_program_metrics_are_benchmark_entries():
    """The tool's metrics are written as BENCHMARK.json's per-layer entries
    are, each with a reader."""
    import attribute
    bench = harness.load_json(util.ROOT / "BENCHMARK.json")
    keys = set(bench["per_layer"][0]) - {"workloads"}
    for m in attribute.PROGRAM_METRICS:
        assert set(m) == keys
        assert (util.HERE / "metrics" / f"{m['name']}.py").exists()
    assert {m["name"] for m in attribute.PROGRAM_METRICS} == set(READERS)



def recorded_with_spans() -> dict:
    return program_trace.reduce(program_trace.load(
        str(RECORDED_SPANS), harness.SPANS))


def test_recorded_chip_trace_with_program_spans():
    """64^3 grid, od 4, two iterations, one v5e, with the program's spans:
    one submit, launch, dispatch and retire per task (8 faces and 4 updates
    an iteration), one host access, download and assembly copy per chunk,
    one split."""
    r = recorded_with_spans()
    counts = {name: n for name, (n, _) in r["program_spans"].items()}
    for name in ("rt.submit", "rt.launch", "rt.dispatch", "rt.retire"):
        assert counts[name] == 2 * 12, name
    assert counts["rt.get"] == counts["rt.d2h"] == 4
    assert counts["jacobi.assemble"] == 4 and counts["jacobi.split"] == 1
    assert r["modules"]["jit_update_kernel"][0] == 2 * 4
    # the ten largest of the idle gaps' names, most of them the program's
    gaps = dict(r["top_gaps"])
    idle = r["window_s"] - r["busy_s"]
    assert len(gaps) == trace_reduce.TOP and sum(gaps.values()) <= idle
    program = sum(v for k, v in gaps.items()
                  if k.startswith(program_trace.PREFIXES))
    assert program > 0.9 * idle


def test_attribute_runs_a_cell_with_the_program_metrics(tmp_path,
                                                        monkeypatch):
    """The tool runs a cell as ``run.py --trace 1`` does, with the
    scheduler's counter taken and the program's metrics read; the harness is
    left as it was. The CPU writes no device plane: the recorded chip
    trace's reduction stands in."""
    import attribute
    here = util.copy_bench(tmp_path)
    monkeypatch.setattr(program_trace, "reduce_dir",
                        lambda *_: recorded_with_spans())
    saved = harness.load_module, harness.trace_reduce
    out = attribute.run_cell(
        f"{util.SMALL}.od4", 2 ** 31 + 9, 0.0,
        bench=harness.load_json(tmp_path / "BENCHMARK.json"),
        trace_dir=str(tmp_path / "trace"), here=here, platform="cpu")
    assert (harness.load_module, harness.trace_reduce) == saved
    assert set(READERS) <= set(out["metrics"])
    assert out["correct"] and out["xplane_bytes"] > 0
    assert out["breakdown"]["program_spans"]["rt.submit"][0] == 2 * 12
