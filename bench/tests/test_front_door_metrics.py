"""The readers of the program's front-door phase spans, call counter and
greedy kernel names (``rerank.shortlist_ms.feed``,
``rerank.greedy_ms.feed``, ``device_nongreedy_ms.batch``), on a trace
made by hand, and the span readers on a CPU rehearsal's trace."""
import pytest

from bench import harness, xplane
from bench.tests.test_harness import rehearse

MS = 1_000_000  # ns

CALLS = {"serving_rerank_calls_total": {"path=batched": 2.0}}


def hand_ctx(ops=None, counters=CALLS, host=None):
    """Two calls in a 10 ms window on two chips."""
    if host is None:
        host = [[("bench.window", 0, 10 * MS),
                 ("serving.rerank", 1 * MS, 4 * MS),
                 ("serving.rerank.shortlist", 1 * MS, 2 * MS),
                 ("serving.rerank.greedy", 2 * MS, 4 * MS),
                 ("serving.rerank", 5 * MS, 9 * MS),
                 ("serving.rerank.shortlist", 5 * MS, 7 * MS),
                 ("serving.rerank.greedy", 7 * MS, 8 * MS),
                 # a call cut by the window's end counts for nothing
                 ("serving.rerank.shortlist", 9 * MS, 11 * MS)]]
    if ops is None:
        step = ('%dpp_step_exact.7 = (f32[1,1,8]) custom-call(v), '
                'custom_call_target="tpu_custom_call"')
        ops = {0: [("%fusion.1 = f32[8] fusion(x)", 1 * MS, 3 * MS),
                   ("%while.2 = (f32[8]) while(z)", 3 * MS, 6 * MS),
                   (step, 3 * MS, 4 * MS),  # inside the while
                   (step, 5 * MS, 6 * MS)],
               1: [("%fusion.1 = f32[8] fusion(x)", 1 * MS, 2 * MS),
                   ("%dpp_resident_windowed.3 = f32[8] custom-call(v), "
                    'custom_call_target="tpu_custom_call"', 2 * MS, 3 * MS)]}
    trace = xplane.Trace(ops, host, (0, 10 * MS))
    return harness.Ctx(trace, counters, 2, None, [0, 1])


def read(name, ctx):
    return harness.load_reader(name)(ctx)


def test_shortlist_ms_is_the_spans_per_counted_call():
    assert read("rerank.shortlist_ms.feed", hand_ctx()) == \
        pytest.approx((1 + 2) / 2)


def test_greedy_ms_is_the_spans_per_counted_call():
    assert read("rerank.greedy_ms.feed", hand_ctx()) == \
        pytest.approx((2 + 1) / 2)


def test_device_nongreedy_ms_is_busy_less_the_greedy_kernels():
    # chip 0: busy [1, 6] = 5 ms, kernels 2 ms; chip 1: busy 2 ms,
    # kernels 1 ms; averaged over the chips, over 2 calls
    assert read("device_nongreedy_ms.batch", hand_ctx()) == \
        pytest.approx(((5 - 2) + (2 - 1)) / 2 / 2)


@pytest.mark.parametrize("name", ["rerank.shortlist_ms.feed",
                                  "rerank.greedy_ms.feed",
                                  "device_nongreedy_ms.batch"])
def test_no_counter_reads_nothing(name):
    assert read(name, hand_ctx(counters={})) is None


@pytest.mark.parametrize("name", ["rerank.shortlist_ms.feed",
                                  "rerank.greedy_ms.feed"])
def test_no_span_reads_nothing(name):
    host = [[("bench.window", 0, 10 * MS), ("serving.rerank", 1 * MS,
                                            4 * MS)]]
    assert read(name, hand_ctx(host=host)) is None


def test_unnamed_kernels_read_nothing():
    """A program whose kernels carry no family name (the Pallas op named
    after its call site) gives no device_nongreedy_ms."""
    ops = {d: [("%closed_call.22 = f32[8] custom-call(v), "
                'custom_call_target="tpu_custom_call"', 1 * MS, 2 * MS),
               ("%fusion.1 = f32[8] fusion(x)", 2 * MS, 3 * MS)]
           for d in (0, 1)}
    assert read("device_nongreedy_ms.batch", hand_ctx(ops=ops)) is None


def test_the_span_readers_read_a_rehearsal_trace():
    """The program's spans reach the profiler under the names the
    readers look for (a CPU rehearsal: the numbers are not timings of
    any chip)."""
    line = rehearse("feed1k.rerank-batch8", trace=1)
    assert line["correct"], line["checks"]
    for name in ("rerank.shortlist_ms.feed", "rerank.greedy_ms.feed"):
        assert line["metrics"][name]["value"] > 0
