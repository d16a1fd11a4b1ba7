"""The trace reduction, on events made by hand and on a trace recorded
on a TPU v5e (``bench/tests/data/``)."""
import glob
import os

import pytest

from bench import xplane
from bench.tests.conftest import ROOT


def hand_trace():
    ops = {0: [("%fusion.1 = f32[8] fusion(x)", 100, 200),
               ("%fusion.2 = f32[8] fusion(y)", 150, 250),  # overlaps
               ("%while.7 = (f32[8]) while(z)", 390, 530),  # holds two
               ("%closed_call.3 = f32[8] custom-call(v), custom_call_target"
                '="tpu_custom_call"', 400, 500),
               ("%all-reduce.4 = f32[8] all-reduce(w)", 480, 520),
               ("%fusion.5 = f32[8] fusion(u)", 900, 1200)]}  # past the end
    host = [[("bench.window", 50, 1000), ("router.pump", 60, 600),
             ("router.pump.admit", 300, 390), ("router.pump", 600, 990)],
            [("ReadSyncFlag", 700, 750)]]
    return xplane.Trace(ops, host, (50, 1000))


def test_busy_is_the_union_of_op_intervals_in_the_window():
    tr = hand_trace()
    # [100,250] + [390,530] + [900,1000]
    assert tr.busy_ns(0) == 150 + 140 + 100
    assert tr.window_ns == 950


def test_op_time_count_and_top_ops():
    tr = hand_trace()
    assert tr.op_ns(0, r"^%fusion") == 100 + 100 + 100
    assert tr.op_ns(0, xplane.PALLAS) == 100
    assert tr.op_count(0, r"^%all-reduce") == 1
    # the while loop holds two ops: only the leaves count
    assert tr.top_ops(0) == [["fusion", 300 / 1e9],
                             ["pallas:closed_call", 100 / 1e9],
                             ["all-reduce", 40 / 1e9]]


def test_idle_gaps_are_named_by_the_innermost_host_span():
    tr = hand_trace()
    gaps = tr.idle_gaps(0)
    # [530,900] is the longest; its middle (715) lies in the second pump
    # while the host waited on a flag
    assert gaps[0] == ["router.pump > ReadSyncFlag", 370 / 1e9]
    # [250,390]: middle 320 lies in the admit span inside the first pump
    assert ["router.pump.admit", 140 / 1e9] in gaps
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_spans_inside_the_window():
    tr = hand_trace()
    assert sorted(tr.spans("router.pump")) == [390, 540]


TRACES = sorted(glob.glob(os.path.join(ROOT, "bench", "tests", "data",
                                       "*.xplane.pb")))


@pytest.mark.parametrize("path", TRACES)
def test_a_recorded_chip_trace(path):
    tr = xplane.Trace.load(path)
    assert 0 in tr.ops and tr.window_ns > 0
    busy = tr.busy_ns(0)
    assert 0 < busy <= tr.window_ns
    total = sum(s for _, s in tr.top_ops(0, n=10 ** 6))
    assert busy / 1e9 <= total + 1e-12  # overlap only shrinks the union
    gaps = tr.idle_gaps(0, n=10 ** 6)
    assert sum(g for _, g in gaps) == pytest.approx(
        (tr.window_ns - busy) / 1e9, rel=1e-9, abs=1e-12)


def test_the_batch8_trace_reads_as_recorded():
    """rerank-batch8 on one v5e, 0.3 s traced: 8 whole calls, each one
    resident greedy kernel."""
    tr = xplane.Trace.load(os.path.join(
        ROOT, "bench", "tests", "data", "rerank-batch8.v5e.xplane.pb"))
    assert tr.window_ns == 299006224
    assert tr.busy_ns(0) == 3794690
    assert tr.op_count(0, xplane.PALLAS) == 8
    assert tr.op_ns(0, xplane.PALLAS) == 1736792
    assert tr.top_ops(0)[0] == ["pallas:dpp_greedy_kernel", 0.001736792]
    assert len(tr.spans("serving.rerank")) == 8
    assert all(g[0].startswith(("serving.rerank", "none"))
               for g in tr.idle_gaps(0))
