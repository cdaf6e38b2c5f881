"""`trace_reduce` against made-up lanes and against one small trace
recorded on a TPU v5e (`testdata/small.xplane.pb`: six executions of a
four-matmul program, the host sleeping 2 ms in `pull_batch` before each).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_trace_reduce.py -q
"""

from __future__ import annotations

import os

import pytest

from benchmarks import trace_reduce

SMALL = os.path.join(os.path.dirname(__file__), "testdata", "small.xplane.pb")


def test_union_merges_and_clips():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert trace_reduce.union([(0, 10), (20, 30)], 5, 25) == [[5, 10], [20, 25]]
    assert trace_reduce.union([(0, 1)], 5, 25) == []


def test_steady_span_runs_from_first_to_last_step():
    modules = [("step", 0, 9), ("step", 20, 30), ("step", 40, 50),
               ("save", 60, 61)]
    ops = [("a", 0, 4), ("b", 4, 9), ("a", 20, 24), ("b", 26, 30),
           ("a", 40, 50), ("c", 60, 61)]
    notes = [("pull_batch", 9, 20), ("train_call", 24, 40)]
    out = trace_reduce.reduce_plane(modules, ops, notes)
    assert out["step_program"] == "step" and out["steps"] == 2
    assert out["span_s"] == pytest.approx(40e-9)
    assert out["span_busy_s"] == pytest.approx(17e-9)
    assert out["busy_s"] == pytest.approx(28e-9)
    assert out["idle_gaps"][:2] == [
        ["pull_batch", pytest.approx(11e-9)],
        ["train_call", pytest.approx(10e-9)],
    ]
    assert out["idle_gaps"][2] == ["train_call", pytest.approx(2e-9)]
    assert out["device_ops"][0] == ["a", pytest.approx(18e-9)]
    assert out["program_s"]["step"] == pytest.approx(29e-9)


def test_a_plane_with_no_operation_reads_nothing():
    assert "busy_s" not in trace_reduce.reduce_plane([], [], [])


def test_the_recorded_trace():
    out = trace_reduce.reduce_file(SMALL)
    assert out["device_planes"] == ["/device:TPU:0"]
    lead = out["lead"]
    assert lead["step_program"].startswith("jit_small_step")
    assert lead["steps"] == 5 and len(lead["step_runs_s"]) == 6
    # 7.5 us of matmuls every 3.2 ms: the chip idles under `pull_batch`.
    assert 7e-6 < min(lead["step_runs_s"]) <= max(lead["step_runs_s"]) < 8e-6
    assert 0.0 < lead["span_busy_s"] / lead["span_s"] < 0.01
    assert [name for name, _ in lead["idle_gaps"]] == ["pull_batch"] * 5
    assert lead["device_ops"][0][0].startswith("convolution_tanh_fusion")
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
