"""The reduction from a profiler trace to busy time, kernel time and
idle gaps: synthetic interval lists for the arithmetic, and the event
list recorded from the first traced chip run of PR 23."""

import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "impala_nature.hostloop.trace.json")
DEV = "/device:TPU:0"


def test_union_merges_overlaps_and_nesting():
    assert tr.union([(0, 10), (5, 12), (20, 30), (22, 25), (30, 31)]) == \
        [(0, 12), (20, 31)]
    assert tr.union([(3, 3), (5, 4)]) == []
    assert tr.union([(0, 10), (20, 30)], lo=5, hi=25) == [(5, 10), (20, 25)]
    assert tr.union_seconds([(0, 1e9), (5e8, 2e9)]) == pytest.approx(2.0)


def _trace(rows):
    return tr.Trace("tpu", rows, {}, mark_ns=0.0)


def test_busy_is_a_union_on_the_op_line_not_a_sum_over_lines():
    rows = [[DEV, "XLA Ops", "fusion.1", 0.0, 4e8],
            [DEV, "XLA Ops", "fusion.2", 5e8, 4e8],
            [DEV, "XLA Modules", "jit_learn", 0.0, 9e8],   # nests the ops
            [DEV, "Steps", "0", 0.0, 9e8]]                 # nests the module
    busy = tr.device_busy(_trace(rows), window_s=1.0, chips=1)
    assert busy["busy_s"] == pytest.approx(0.8)
    # what PR 22 did: add every line's durations -> 2.6 s in a 1 s window
    assert sum(r[4] for r in rows) / 1e9 == pytest.approx(2.6)


def test_nested_ops_on_the_op_line_count_once():
    rows = [[DEV, "XLA Ops", "while", 0.0, 9e8],
            [DEV, "XLA Ops", "fusion.1", 1e8, 2e8],
            [DEV, "XLA Ops", "fusion.1", 4e8, 2e8]]
    t = _trace(rows)
    assert tr.device_busy(t, 1.0, 1)["busy_s"] == pytest.approx(0.9)
    totals = tr.op_totals(t)
    assert totals["while"][0] == pytest.approx(0.9)
    assert totals["while"][2] == pytest.approx(0.5)  # self time
    assert tr.top_ops(totals)[0][0] == "while"
    assert tr.match_totals(totals, {}, r"^fusion") == (pytest.approx(0.4), 2)


def test_several_devices_are_averaged_one_union_each():
    rows = [[DEV, "XLA Ops", "a", 0.0, 4e8],
            ["/device:TPU:1", "XLA Ops", "a", 0.0, 8e8]]
    busy = tr.device_busy(_trace(rows), 1.0, chips=2)
    assert busy["busy_s"] == pytest.approx(0.6)
    with pytest.raises(tr.TraceError, match="asks for 1"):
        tr.device_busy(_trace(rows), 1.0, chips=1)


@pytest.mark.parametrize("rows, why", [
    ([], "no device operation"),
    ([[DEV, "XLA Modules", "jit_learn", 0.0, 1e8]], "no device operation"),
    ([["/host:CPU", "python", "x", 0.0, 1e8]], "no device operation"),
    ([[DEV, "XLA Ops", "a", 0.0, 2e9]], "span"),  # longer than the window
])
def test_a_trace_that_cannot_give_a_sound_number_is_an_error(rows, why):
    with pytest.raises(tr.TraceError, match=why):
        tr.device_busy(_trace(rows), window_s=1.0, chips=1)


def test_two_op_lines_on_one_tpu_plane_are_refused():
    t = tr.Trace("tpu", [[DEV, "XLA Ops", "a", 0.0, 1e8]], {})
    tr.DEVICE_LINES["tpu2"] = {"plane": r"^/device:TPU:\d+$", "line": "XLA"}
    try:
        t2 = tr.Trace("tpu2", t.events + [[DEV, "XLA Modules", "m", 0.0, 1e8]], {})
        with pytest.raises(tr.TraceError, match="2 lines"):
            tr.op_events(t2)
    finally:
        del tr.DEVICE_LINES["tpu2"]


def test_idle_gaps_are_named_by_the_innermost_host_span():
    rows = [[DEV, "XLA Ops", "a", 0.0, 1e8],      # busy 0.0-0.1
            [DEV, "XLA Ops", "a", 6e8, 1e8],      # gap 0.1-0.6
            [DEV, "XLA Ops", "a", 9e8, 1e8]]      # gap 0.7-0.9
    spans = [("publish", 100.0, 100.65), ("publish_stall", 100.2, 100.5),
             ("dequeue", 100.7, 100.95)]
    gaps = dict(tr.idle_gaps(_trace(rows), spans, trace_start_wall_s=100.0))
    assert gaps == {"publish_stall": pytest.approx(0.5),
                    "dequeue": pytest.approx(0.2)}
    assert tr.idle_gaps(_trace(rows), [], 100.0) == [
        ["unattributed", pytest.approx(0.7)]]


# ------------------------------------------------- the recorded fixture


@pytest.fixture(scope="module")
def recorded():
    assert os.path.getsize(FIXTURE) < 1_000_000
    return tr.load(FIXTURE)


def test_recorded_trace_has_the_lines_the_rule_names(recorded):
    assert recorded.platform == "tpu"
    lines = {(r[0], r[1]) for r in recorded.events}
    assert (DEV, "XLA Ops") in lines
    assert len({ln for _, ln in lines}) >= 2, "only the op line was recorded"
    assert list(tr.op_events(recorded)) == [DEV]


def test_recorded_trace_nested_lines_would_overshoot(recorded):
    """On the chip's own trace: the union over the op line stays inside
    the span of the events, while the sum over all lines does not stay
    under the op line's union — the fault the union rule exists for."""
    rows = tr.op_events(recorded)[DEV]
    lo = min(r[3] for r in recorded.events)
    hi = max(r[3] + r[4] for r in recorded.events)
    span_s = (hi - lo) / 1e9
    busy = tr.device_busy(recorded, window_s=span_s, chips=1)
    assert 0 < busy["busy_s"] <= span_s
    assert busy["busy_s"] == pytest.approx(
        tr.union_seconds([(r[3], r[3] + r[4]) for r in rows]))
    all_lines_sum = sum(r[4] for r in recorded.events) / 1e9
    assert all_lines_sum > busy["busy_s"] * 1.5
    inv = {(i["plane"], i["line"]): i for i in tr.lines_inventory(recorded)}
    assert inv[(DEV, "XLA Ops")]["union_s"] == pytest.approx(busy["busy_s"])


def test_recorded_trace_names_the_vtrace_kernel(recorded):
    with open(os.path.join(os.path.dirname(HERE), "layer_metrics",
                           "vtrace_roofline.json")) as f:
        import json
        pattern = json.load(f)["source_detail"]["pattern"]
    seconds, calls = tr.event_seconds(recorded, pattern)
    # two updates of two kernels each; the fusion that consumes their
    # output (347 ns a time) names them as operands and is not counted
    assert calls == 4
    assert seconds == pytest.approx((88 + 267 + 89 + 263) * 1e-9)
    assert seconds < tr.device_busy(recorded, 1e9, 1)["busy_s"]
