"""The reference copy agrees with chip_smoke.py's, and ``follow`` reads
a slate the way the check needs."""
import importlib.util
import os

import numpy as np
import pytest

from bench import reference
from bench.tests.conftest import ROOT


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pool(seed, M=400, D=24):
    rng = np.random.default_rng(seed)
    feats = rng.standard_normal((M, D), dtype=np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True)
    return feats, rng.standard_normal(M, dtype=np.float32)


@pytest.mark.parametrize("window", [None, 4])
@pytest.mark.parametrize("masked", [False, True])
def test_copy_matches_chip_smoke(smoke, window, masked):
    feats, scores = pool(1)
    mask = None
    if masked:
        mask = np.ones(scores.size, bool)
        mask[::4] = False
    want = smoke.ref_rerank(feats, scores, 100, 20, window, mask)
    got = reference.ref_rerank(feats, scores, 100, 20, smoke.ALPHA,
                               smoke.EPS, window, mask)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(reference.shortlist(scores, 50, mask),
                                  smoke.shortlist(scores, 50, mask))


@pytest.mark.parametrize("window", [None, 4])
def test_follow_reads_zero_on_the_reference_slate(window):
    feats, scores = pool(2)
    ids, gains = reference.ref_rerank(feats, scores, 120, 20, 3.0, 1e-3,
                                      window)
    f = reference.follow(feats, scores, 120, ids, gains, 3.0, 1e-3, window)
    assert f.invalid is None and f.steps == 20
    assert f.pick_gap == 0.0
    assert f.gain_err < 1e-12


@pytest.mark.parametrize("window", [None, 4])
def test_follow_sees_a_wrong_pick_and_a_wrong_gain(window):
    feats, scores = pool(3)
    ids, gains = reference.ref_rerank(feats, scores, 120, 20, 3.0, 1e-3,
                                      window)
    swapped = ids.copy()
    swapped[[3, 9]] = swapped[[9, 3]]
    f = reference.follow(feats, scores, 120, swapped, gains, 3.0, 1e-3,
                         window)
    assert f.pick_gap > 1e-3
    off = gains.copy()
    off[5] *= 1.01
    f = reference.follow(feats, scores, 120, ids, off, 3.0, 1e-3, window)
    assert f.pick_gap == 0.0 and 0.009 < f.gain_err < 0.011


def test_follow_names_structural_faults():
    feats, scores = pool(4)
    ids, gains = reference.ref_rerank(feats, scores, 120, 10, 3.0, 1e-3)
    sl = reference.shortlist(scores, 120)
    outside = np.setdiff1d(np.arange(scores.size), sl)[0]
    cases = {
        "repeated": np.r_[ids[:5], ids[4], ids[6:]],
        "not in the shortlist": np.r_[ids[:5], outside, ids[6:]],
        "-1 before": np.r_[ids[:5], -1, ids[6:]],
    }
    for what, bad in cases.items():
        f = reference.follow(feats, scores, 120, bad, gains, 3.0, 1e-3)
        assert f.invalid is not None, what
    mask = np.ones(scores.size, bool)
    mask[ids[2]] = False
    f = reference.follow(feats, scores, 120, ids, gains, 3.0, 1e-3,
                         mask=mask)
    assert f.invalid is not None


def test_a_stop_where_the_reference_goes_on_is_a_gap():
    feats, scores = pool(5)
    ids, gains = reference.ref_rerank(feats, scores, 120, 12, 3.0, 1e-3)
    early = ids.copy()
    early[8:] = -1
    f = reference.follow(feats, scores, 120, early, gains, 3.0, 1e-3)
    assert f.invalid is None and f.pick_gap > 0.9


def test_bf16_rounding():
    x = np.array([1.0, 1.0 + 3 * 2 ** -9, 1.0 + 2 ** -9, 1.0 + 2 ** -8,
                  1.0 + 3 * 2 ** -8], np.float32)
    # 8 significant bits; a tie goes to the even neighbour
    np.testing.assert_array_equal(
        reference._bf16(x), [1.0, 1.0 + 2 ** -7, 1.0, 1.0, 1.0 + 2 ** -6])


@pytest.mark.parametrize("window", [None, 4])
def test_the_control_errs_more_than_float32(window):
    feats, scores = pool(7, M=2000, D=64)
    ids, gains = reference.control_rerank(feats, scores, 500, 30, 3.0, 1e-3,
                                          window)
    f = reference.follow(feats, scores, 500, ids, gains, 3.0, 1e-3, window)
    assert f.invalid is None
    assert f.gain_err > 1e-4
