"""The reduction from a profiler trace to busy time, program time and idle
gaps, on made-up events and on a small trace recorded on a v5e chip."""
from __future__ import annotations

import numpy as np
import pytest

import bench_chip_util as util

import trace_reduce


def test_union_merges_overlaps_and_keeps_gaps():
    iv = np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0]])
    assert trace_reduce.union(iv).tolist() == [[0.0, 4.0], [5.0, 6.0]]
    assert trace_reduce.union(np.zeros((0, 2))).shape == (0, 2)


def test_reduce_on_made_up_events():
    raw = {"devices": {
        "/device:TPU:0": {
            "ops": [("fusion", 1.0, 2.0), ("copy", 1.5, 2.5),
                    ("fusion", 6.0, 7.0)],
            "modules": [("jit_update_kernel(7)", 1.0, 2.5),
                        ("jit_update_kernel(7)", 6.0, 7.0)]},
        "/device:TPU:1": {"ops": [("fusion", 0.5, 1.5)], "modules": []}},
        "spans": [("solve", 0.0, 10.0), ("run", 0.5, 1.0),
                  ("get", 3.0, 5.0), ("runtime_shutdown", 8.0, 9.5)]}
    r = trace_reduce.reduce(raw)
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx((2.5 + 1.0) / 2)
    assert r["modules"] == {"jit_update_kernel": (2, 2.5)}
    assert r["top_ops"][0] == ["fusion", 1.5]
    gaps = dict(r["top_gaps"])
    # chip 0 is idle 0-1 (middle 0.5, in run), 2.5-6 (middle 4.25, in get)
    # and 7-10 (middle 8.5, in runtime_shutdown); chip 1 is idle 0-0.5 and
    # 1.5-10 (middles 0.25 and 5.75, in solve alone)
    assert gaps["get"] == pytest.approx(3.5 / 2)
    assert gaps["runtime_shutdown"] == pytest.approx(3.0 / 2)
    assert gaps["solve"] == pytest.approx((0.5 + 8.5) / 2)
    assert gaps["run"] == pytest.approx(1.0 / 2)
    assert sum(gaps.values()) == pytest.approx(10.0 - r["busy_s"])


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no TPU"):
        trace_reduce.reduce({"devices": {}, "spans": []})


def test_recorded_chip_trace():
    """64^3 grid, od 4 (four 32x32x64 chunks), two iterations, one v5e."""
    raw = trace_reduce.load(str(util.RECORDED), util.harness.SPANS)
    assert list(raw["devices"]) == ["/device:TPU:0"]
    assert [s[0] for s in raw["spans"]].count("solve") == 1
    r = trace_reduce.reduce(raw)
    assert r["modules"]["jit_update_kernel"][0] == 2 * 4
    # two faces per chunk in a (2, 2, 1) chunk grid, every iteration
    assert r["modules"]["jit_extract"][0] == 2 * 4 * 2
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = dict(r["top_gaps"])
    assert set(gaps) <= set(util.harness.SPANS) | {"outside"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["top_ops"] and all(not n.startswith("%") or " = " not in n
                                for n, _ in r["top_ops"])
