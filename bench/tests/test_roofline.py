"""The greedy's operations and bytes against shapes worked by hand, and
the peaks table."""
import pytest

from bench import roofline


def test_ops_by_hand():
    # one user, M=1000, D=100, k=2, exact: step 0 conditions on 0 rows,
    # step 1 on 1 row; per step 2DM + 2rM + M + 2M + M
    step0 = 2 * 100 * 1000 + 0 + 1000 + 2000 + 1000
    step1 = 2 * 100 * 1000 + 2 * 1000 + 1000 + 2000 + 1000
    assert roofline.greedy_ops(1, 1000, 100, 2) == step0 + step1
    assert roofline.greedy_ops(3, 1000, 100, 2) == 3 * (step0 + step1)
    # windowed w=1 caps the conditioned rows at 1
    step2 = step1
    assert roofline.greedy_ops(1, 1000, 100, 3, w=1) == step0 + step1 + step2


def test_bytes_small_and_large_by_hand():
    # small: V and the state once per call
    assert roofline.greedy_bytes(2, 1000, 100, 50) == \
        2 * (100 * 1000 * 4 + 50 * 1000 * 4)
    # large (the working set is past on-core memory): one sweep per step
    # of V, the r_t rows read, one row written, d2 read+written, mask read
    M, D, w = 1024, 64, 2
    per = [D * M * 4 + r * M * 4 + M * 4 + 2 * M * 4 + M * 4
           for r in (0, 1, 2, 2)]
    assert roofline.greedy_bytes(4, M, D, 4, w, vmem_bytes=1) == 4 * sum(per)


def test_pool_cells_count_per_step_and_feed_cells_per_call():
    vmem = roofline.PEAKS["TPU v5 lite"]["vmem_bytes"]
    small = roofline.greedy_bytes(8, 1000, 100, 50, None, vmem)
    assert small == 8 * (100 * 1000 + 50 * 1000) * 4
    large = roofline.greedy_bytes(4, 262144, 64, 50, 8, vmem)
    assert large > 50 * 4 * 64 * 262144 * 4  # at least V every step


def test_least_time_is_the_larger_bound():
    t, bound = roofline.least_seconds("TPU v5 lite", 4, 262144, 64, 50, 8)
    p = roofline.PEAKS["TPU v5 lite"]
    assert bound == "bytes"
    assert t == pytest.approx(roofline.greedy_bytes(
        4, 262144, 64, 50, 8, p["vmem_bytes"]) / p["hbm_bytes_per_s"])


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.least_seconds("TPU v99", 1, 1000, 100, 50)
