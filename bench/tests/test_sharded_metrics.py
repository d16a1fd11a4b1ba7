"""The readers of the mesh's collectives (``sharded.collective_ms``,
``sharded.merge_us``), on a trace made by hand."""
import pytest

from bench import harness, xplane

US = 1_000  # ns

MERGES = {"sharded_merges_total": {"kind=argmax": 4.0, "kind=winner": 4.0}}


def hand_ctx(counters=MERGES):
    """Two calls in a 1 ms window on two chips; chip 0 runs the merges'
    all-reduces and all-gathers (one async, one named ``psum``), a
    collective-permute of a reshard, and ops that are no collective."""
    ops = {0: [("%all-reduce.1 = f32[2] all-reduce(a)", 10 * US, 13 * US),
               ("%all-gather.2 = s32[4] all-gather(b)", 20 * US, 22 * US),
               # the winner broadcast, named after its jax primitive
               ("%psum.7 = f32[52]{0:T(128)S(1)} all-reduce(f32[52]{0:T(128)"
                "S(1)} %broadcast_select_fusion.5), channel_id=1",
                24 * US, 28 * US),
               ("%collective-permute-start.3 = f32[32,8] "
                "collective-permute-start(c)", 30 * US, 50 * US),
               ("%fusion.4 = f32[8] fusion(x)", 60 * US, 90 * US),
               ('%dpp_step_exact.5 = f32[8] custom-call(v), '
                'custom_call_target="tpu_custom_call"', 100 * US, 200 * US)],
           1: [("%all-reduce.1 = f32[2] all-reduce(a)", 10 * US, 40 * US)]}
    async_ops = {0: [("%all-reduce-start.6 = f32[2] all-reduce-start(d)",
                      300 * US, 305 * US)]}
    host = [[("bench.window", 0, 1000 * US)]]
    trace = xplane.Trace(ops, host, (0, 1000 * US), async_ops)
    return harness.Ctx(trace, counters, 2, None, [0, 1])


def read(name, ctx):
    return harness.load_reader(name)(ctx)


def test_merge_us_is_the_first_chips_merge_collectives_per_merge():
    # chip 0: all-reduce 3 us + all-gather 2 us + psum 4 us + async
    # all-reduce 5 us, over 8 merges; the collective-permute is no merge
    assert read("sharded.merge_us", hand_ctx()) == \
        pytest.approx((3 + 2 + 4 + 5) / 8)


def test_collective_ms_is_the_first_chips_collectives_named_so_per_call():
    # matched by instruction name, so the op named psum is not counted
    assert read("sharded.collective_ms", hand_ctx()) == \
        pytest.approx((3 + 2 + 20 + 5) / 1e3 / 2)


def test_merge_us_without_the_counter_reads_nothing():
    assert read("sharded.merge_us", hand_ctx(counters={})) is None
