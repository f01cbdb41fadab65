"""The reduction from trace events to busy, idle, program and op time."""
import pytest

from chipbench import trace


def test_union_merges_overlaps_and_touching():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_gaps_around_busy_intervals():
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert trace.gaps([], 0, 3) == [(0, 3)]


def test_self_times_subtract_nested_ops():
    ev = [("%while.3 = (s32) while(x)", 0.0, 10.0),
          ("%fusion.1 = f32 fusion(a)", 1.0, 2.0),
          ("%fusion.2 = f32 fusion(b)", 4.0, 3.0),
          ("%latency_hist.1 = s32 custom-call(x)", 12.0, 1.0)]
    got = {trace.op_name(n): d for n, _, d in trace.self_times(ev)}
    assert got == {"while.3": 5.0, "fusion.1": 2.0, "fusion.2": 3.0,
                   "latency_hist.1": 1.0}


def test_op_and_program_names():
    assert trace.op_name("%fusion.3 = f32[5] fusion(x)") == "fusion.3"
    assert trace.op_name("jit__transient_batch(1477)") == "jit__transient_batch"


def test_reduce_idle_share_and_gap_attribution():
    ops = [("%while.3 = w", 1.0, 4.0), ("%fusion.1 = f", 2.0, 1.0),
           ("%latency_hist.1 = k", 7.0, 1.0)]
    programs = [("jit__execute_batch(9)", 1.0, 4.0),
                ("jit_latency_hist(4)", 7.0, 1.0)]
    host = [(trace.WINDOW_SPAN, 0.0, 10.0), (trace.ANSWER_SPAN, 0.0, 10.0),
            ("probe", 0.0, 1.0), ("host sums", 5.0, 2.0)]
    s = trace.reduce([{"ops": ops, "programs": programs}], host, (0.0, 10.0))
    assert s.window_s == 10.0 and s.busy_s == 5.0
    assert s.idle_share == pytest.approx(0.5)
    assert s.program_seconds("_execute_batch") == 4.0
    assert s.program_seconds("latency_hist") == 1.0
    assert s.op_seconds("latency_hist") == 1.0
    assert s.op_seconds("fusion") == 1.0
    # the longest gap first, each named by the innermost host span
    assert s.idle == [("host sums", 2.0), (trace.ANSWER_SPAN, 2.0),
                      ("probe", 1.0)]
    b = s.breakdown()
    assert b["device_ops"][0] == ["while.3", 3.0]
    assert b["idle_gaps"][0] == ["host sums", 2.0]


def test_reduce_averages_busy_over_chips_and_clips_to_window():
    a = {"ops": [("%x.1 = x", -1.0, 3.0)], "programs": []}
    b = {"ops": [("%x.1 = x", 0.0, 4.0)], "programs": []}
    s = trace.reduce([a, b], [], (0.0, 4.0))
    assert s.busy_s == pytest.approx(3.0)
    assert s.op_s == {"x.1": pytest.approx(6.0)}
