"""`scope_reduce` against hand-made `(tf_op, start, end)` rows and against
the small trace recorded on a TPU v5e (`testdata/small.xplane.pb`).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_scope_reduce.py -q
"""

from __future__ import annotations

import os
import statistics

import pytest

from benchmarks import scope_reduce, trace_reduce

SMALL = os.path.join(os.path.dirname(__file__), "testdata", "small.xplane.pb")
STEP = "jit(adanet_train_step)/jit(main)/"


@pytest.mark.parametrize("tf_op, direction, path, group", [
    (STEP + "jvp(candidate.a)/NasNetA/cell_3/block0_left_sep/pointwise_0/"
     "conv_general_dilated:", "forward",
     ["candidate.a", "NasNetA", "cell_3", "block0_left_sep", "pointwise_0",
      "conv_general_dilated"], "candidate_fwd"),
    (STEP + "transpose(jvp(candidate.a))/NasNetA/cell_3/beginning_bn/mul:",
     "backward",
     ["candidate.a", "NasNetA", "cell_3", "beginning_bn", "mul"],
     "candidate_bwd"),
    # A nested `jvp` (a `custom_jvp` inside the loss) is still forward.
    (STEP + "jvp(jvp(candidate.a))/NasNetA/jit(relu)/max:", "forward",
     ["candidate.a", "NasNetA", "max"], "candidate_fwd"),
    # `iterations_per_loop` > 1: the step is the body of a `while`.
    ("jit(adanet_train_steps)/jit(main)/while/body/closed_call/"
     "transpose(jvp(candidate.a))/NasNetA/stem_conv/conv:", "backward",
     ["candidate.a", "NasNetA", "stem_conv", "conv"], "candidate_bwd"),
    (STEP + "optimizer.a/mul:", None, ["optimizer.a", "mul"], "optimizer"),
    (STEP + "ensemble_optimizer.t0_a_grow/add:", None,
     ["ensemble_optimizer.t0_a_grow", "add"], "optimizer"),
    (STEP + "jvp(ensemble.t0_a_grow)/reduce_sum:", "forward",
     ["ensemble.t0_a_grow", "reduce_sum"], "ensemble"),
    (STEP + "transpose(jvp(ensemble.t0_a_grow))/mul:", "backward",
     ["ensemble.t0_a_grow", "mul"], "ensemble"),
    (STEP + "frozen.t0_a/NasNetA/cell_0/add:", None,
     ["frozen.t0_a", "NasNetA", "cell_0", "add"], "ensemble"),
    (STEP + "step.metrics/select_n:", None, ["step.metrics", "select_n"],
     "unscoped"),
    (STEP + "jit(_threefry_split)/threefry2x32:", None, ["threefry2x32"],
     "unscoped"),
    ("", None, [], "unscoped"),
    (None, None, [], "unscoped"),
])
def test_split_and_group(tf_op, direction, path, group):
    assert scope_reduce.split(tf_op) == (direction, path)
    assert scope_reduce.group(direction, path) == group


@pytest.mark.parametrize("path, kind", [
    (["candidate.a", "NasNetA", "cell_3", "block0_left_sep", "depthwise_0",
      "conv"], "sep.conv"),
    (["candidate.a", "NasNetA", "cell_3", "block0_left_sep", "bn_1", "mul"],
     "sep.bn"),
    (["candidate.a", "NasNetA", "cell_3", "block2_right_1x1", "conv"], "1x1"),
    (["candidate.a", "NasNetA", "cell_3", "block2_right_bn1", "add"],
     "batch_norm"),
    (["candidate.a", "NasNetA", "cell_3", "beginning_bn", "add"],
     "batch_norm"),
    (["candidate.a", "NasNetA", "cell_6", "reduce_prev", "path1_conv", "conv"],
     "factorized_reduction"),
    (["candidate.a", "NasNetA", "cell_6", "reduction_1", "final_path_bn",
      "mul"], "factorized_reduction"),
    (["candidate.a", "NasNetA", "aux_head", "aux_bn1", "mul"], "aux_head"),
    (["candidate.a", "NasNetA", "cell_3", "reduce_window_sum"], "pool"),
    (["candidate.a", "NasNetA", "cell_3", "select_and_scatter_add"], "pool"),
    (["candidate.a", "NasNetA", "logits", "dot_general"], "other"),
])
def test_branch_kind_of_a_nasnet_path(path, kind):
    assert scope_reduce.kind(path) == kind


def _row(tf_op, start, end):
    return {"tf_op": tf_op, "start": start, "end": end, "name": "op"}


ROWS = [
    # Two whole steps of 100 ns in the span [0, 200); a third begins it.
    _row(STEP + "jvp(candidate.a)/M/cell_0/x_sep/depthwise_0/conv:", 0, 30),
    _row(STEP + "transpose(jvp(candidate.a))/M/cell_0/x_sep/bn_0/mul:",
         30, 70),
    _row(STEP + "optimizer.a/add:", 70, 80),
    _row(STEP + "jvp(ensemble.t0_a)/mul:", 80, 85),
    _row(None, 85, 95),
    _row(STEP + "step.metrics/select_n:", 95, 100),
    _row(STEP + "jvp(candidate.a)/M/cell_1/y_1x1/conv:", 100, 160),
    _row(STEP + "transpose(jvp(candidate.a))/M/aux_head/proj/conv:",
         160, 200),
    _row(STEP + "jvp(candidate.a)/M/cell_0/x_sep/depthwise_0/conv:",
         200, 230),
]


def test_the_five_groups_sum_to_the_total():
    out = scope_reduce.reduce_rows(ROWS, 0, 200, 2)
    groups = out["groups_ms"]
    assert set(groups) == set(scope_reduce.GROUPS)
    per_step = 1e-6 / 2
    assert groups["candidate_fwd"] == pytest.approx(90 * per_step)
    assert groups["candidate_bwd"] == pytest.approx(80 * per_step)
    assert groups["optimizer"] == pytest.approx(10 * per_step)
    assert groups["ensemble"] == pytest.approx(5 * per_step)
    # No `tf_op`, and the metrics tail: under none of the groups.
    assert groups["unscoped"] == pytest.approx(15 * per_step)
    assert sum(groups.values()) == pytest.approx(200 * per_step)
    assert out["total_ms"] == pytest.approx(sum(groups.values()))
    # Forward and backward alike under a `_SepConv` instance.
    assert out["sepconv_ms"] == pytest.approx(70 * per_step)
    assert dict(out["modules_ms"])["M/cell_0/x_sep"] == pytest.approx(
        70 * per_step
    )
    assert dict(out["unscoped_ms"]) == {
        "(no category): (no tf_op)": pytest.approx(10 * per_step),
        "(no category): step.metrics/select_n": pytest.approx(5 * per_step),
    }
    assert dict(out["kinds_ms"]) == {
        "sep.conv": pytest.approx(30 * per_step),
        "sep.bn": pytest.approx(40 * per_step),
        "1x1": pytest.approx(60 * per_step),
        "aux_head": pytest.approx(40 * per_step),
    }


def test_an_operation_that_holds_others_is_charged_what_they_leave():
    rows = [
        _row("jit(s)/while:", 0, 100),
        _row("jit(s)/while/body/jvp(candidate.a)/M/dot:", 10, 40),
        _row("jit(s)/while/body/optimizer.a/add:", 40, 60),
    ]
    times = {e["tf_op"]: t for e, t in scope_reduce.self_times(rows)}
    assert times["jit(s)/while:"] == pytest.approx(50)
    out = scope_reduce.reduce_rows(rows, 0, 100, 1)
    assert out["groups_ms"]["unscoped"] == pytest.approx(50e-6)
    assert out["total_ms"] == pytest.approx(100e-6)


def test_a_program_without_the_scopes_reads_none():
    """The parent commit's step: Flax's module path, no scope of ours."""
    rows = [
        _row("jit(_train_step_impl)/jit(main)/jvp(NasNetA)/cell_0/conv:",
             0, 50),
        _row("jit(_train_step_impl)/jit(main)/mul:", 50, 100),
    ]
    assert scope_reduce.reduce_rows(rows, 0, 100, 1) is None
    assert scope_reduce.group_ms(
        {"scope_reduce": {"scopes": None}}, "unscoped"
    ) is None
    assert scope_reduce.group_ms({"scope_reduce": None}, "unscoped") is None


def test_a_run_that_was_not_traced_looks_for_no_file():
    record = {"trace": None}
    assert scope_reduce.of_record(record) is None
    assert scope_reduce.group_ms(record, "optimizer") is None


def test_the_recorded_trace_carries_tf_op_in_event_metadata():
    space = scope_reduce.load_space(SMALL)
    [plane] = [p for p in space.planes if p.name.startswith("/device:TPU:")]
    ops = scope_reduce.plane_lines(plane)[trace_reduce.OP_LINE]
    fusions = [e for e in ops if e["hlo_category"] == "convolution fusion"]
    assert len(fusions) == 6 * 2  # six runs of the program
    for event in fusions:
        assert event["tf_op"] == "jit(small_step)/dot_general:"
        assert scope_reduce.split(event["tf_op"]) == (None, ["dot_general"])
        assert event["end"] > event["start"]
    [program] = {e["program_id"] for e in ops}
    modules = scope_reduce.plane_lines(plane)[trace_reduce.MODULE_LINE]
    assert {m["name"] for m in modules} == {"jit_small_step(%d)" % program}


def test_the_recorded_trace_agrees_with_trace_reduce():
    """The proto read here and `ProfileData` give the same step, and a
    program with no scope of ours reads None, not zeros."""
    out = scope_reduce.reduce_file(SMALL)
    lead = trace_reduce.reduce_file(SMALL)["lead"]
    assert out["scopes"] is None
    # `ProfileData` hands out whole nanoseconds, the proto picoseconds.
    assert out["step_device_ms"] * 1e-3 == pytest.approx(
        statistics.median(lead["step_runs_s"]), abs=2e-9
    )
    # The harness's `pull_batch` is there; none of the program's spans.
    assert out["host_spans"] == dict.fromkeys(scope_reduce.HOST_SPANS, 0)
    assert out["dispatch_lead_ms"] is None


def test_host_spans_are_read_back_on_the_device_lanes_axis(monkeypatch):
    """The recorded trace's host plane holds the harness's `pull_batch`
    and `train_call`: read as if they were the program's, they are
    counted where they start before the device's last op ends, and the
    lead is the device's first op less the first dispatch."""
    monkeypatch.setattr(
        scope_reduce, "HOST_SPANS", ("pull_batch", "train_window")
    )
    out = scope_reduce.reduce_file(SMALL)
    assert out["host_spans"] == {"pull_batch": 6, "train_window": 0}
    assert out["dispatch_lead_ms"] is None
