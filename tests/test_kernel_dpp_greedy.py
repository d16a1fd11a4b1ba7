"""Shape/dtype sweep of the dpp_greedy Pallas kernel (interpret mode)
against the pure-jnp oracle (inputs and the shared ``greedy_oracle``
fixture come from tests/conftest.py)."""
import numpy as np
import pytest
import jax.numpy as jnp

from conftest import assert_greedy_parity, make_greedy_inputs as make_inputs
from repro.kernels.dpp_greedy import (
    VMEM_BUDGET_BYTES,
    dpp_greedy,
    dpp_greedy_ref,
    plan_tile,
    untiled_vmem_bytes,
)


@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("D,M", [(16, 64), (32, 256), (64, 512)])
@pytest.mark.parametrize("k", [4, 16])
def test_kernel_matches_ref_sweep(B, D, M, k):
    V = make_inputs(B * 7 + D + M + k, B, D, M)
    sel_k, dh_k = dpp_greedy(V, k, interpret=True)
    sel_r, dh_r = dpp_greedy_ref(V, jnp.ones((B, M), bool), k)
    np.testing.assert_array_equal(np.asarray(sel_k), np.asarray(sel_r))
    np.testing.assert_allclose(np.asarray(dh_k), np.asarray(dh_r), rtol=3e-4, atol=1e-6)


@pytest.mark.parametrize("window", [None, 4])
def test_kernel_matches_shared_oracle(greedy_oracle, window):
    """Both resident kernels (exact + windowed) against the one shared
    oracle fixture — the same ground truth every other backend suite
    asserts against."""
    B, D, M, k = 2, 16, 96, 8
    V = make_inputs(61, B, D, M)
    rng = np.random.default_rng(2)
    mask = jnp.asarray(rng.uniform(size=(B, M)) > 0.25)
    sel, dh = dpp_greedy(V, k, mask=mask, window=window, interpret=True)
    assert_greedy_parity(greedy_oracle, sel, dh, V, k, window=window,
                         eps=1e-3, mask=mask)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    V = make_inputs(3, 2, 16, 128, dtype=dtype)
    sel_k, _ = dpp_greedy(V, 8, interpret=True)
    sel_r, _ = dpp_greedy_ref(V.astype(jnp.float32), jnp.ones((2, 128), bool), 8)
    # bf16 inputs are upcast to f32 inside both paths; selections must agree
    np.testing.assert_array_equal(np.asarray(sel_k), np.asarray(sel_r))


def test_kernel_mask():
    B, D, M, k = 2, 16, 128, 8
    V = make_inputs(11, B, D, M)
    rng = np.random.default_rng(0)
    mask = jnp.asarray(rng.uniform(size=(B, M)) > 0.3)
    sel_k, _ = dpp_greedy(V, k, mask=mask, interpret=True)
    sel_r, _ = dpp_greedy_ref(V, mask, k)
    np.testing.assert_array_equal(np.asarray(sel_k), np.asarray(sel_r))
    for b in range(B):
        valid = np.asarray(sel_k[b])
        valid = valid[valid >= 0]
        assert np.asarray(mask[b])[valid].all()


def test_kernel_eps_stop():
    """Rank-deficient: kernel must stop exactly where the oracle stops."""
    B, D, M, k = 1, 6, 128, 16
    V = make_inputs(13, B, D, M)
    sel_k, dh_k = dpp_greedy(V, k, eps=1e-3, interpret=True)
    sel_r, dh_r = dpp_greedy_ref(V, jnp.ones((B, M), bool), k, eps=1e-3)
    np.testing.assert_array_equal(np.asarray(sel_k), np.asarray(sel_r))
    n = int((np.asarray(sel_k) >= 0).sum())
    assert n <= D + 2


def test_kernel_nonaligned_padding():
    """M, D not multiples of (128, 8): ops.py pads; result unchanged."""
    B, D, M, k = 2, 19, 200, 5
    V = make_inputs(17, B, D, M)
    sel_k, _ = dpp_greedy(V, k, interpret=True)
    sel_r, _ = dpp_greedy_ref(V, jnp.ones((B, M), bool), k)
    np.testing.assert_array_equal(np.asarray(sel_k), np.asarray(sel_r))


def test_dispatch_past_gate_is_tiled_not_jnp():
    """Huge M no longer falls back to jnp — plan_tile dispatches the
    tiled streaming kernels; the jnp path needs an explicit force_jnp."""
    B, D, M, k = 1, 8, 4096, 4
    assert untiled_vmem_bytes(64, 1 << 20, 32) > VMEM_BUDGET_BYTES
    mode, tile = plan_tile(64, 1 << 20, 32, windowed=False)
    assert mode == "tiled" and tile is not None
    V = make_inputs(19, B, D, M)
    sel, _ = dpp_greedy(V, k, force_jnp=True)
    assert int((np.asarray(sel) >= 0).sum()) == k


# ---------------------------------------------------------------------------
# Sliding-window kernel mode (C shrinks to a (w, M) VMEM ring; N unbounded)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B", [1, 2])
@pytest.mark.parametrize("D,M,k,w", [(16, 64, 16, 4), (32, 256, 24, 6), (16, 128, 40, 1)])
def test_kernel_windowed_matches_ref(B, D, M, k, w):
    V = make_inputs(B * 5 + D + M + k + w, B, D, M)
    sel_k, dh_k = dpp_greedy(V, k, interpret=True, window=w)
    sel_r, dh_r = dpp_greedy_ref(V, jnp.ones((B, M), bool), k, window=w)
    np.testing.assert_array_equal(np.asarray(sel_k), np.asarray(sel_r))
    np.testing.assert_allclose(np.asarray(dh_k), np.asarray(dh_r), rtol=3e-4, atol=1e-5)


def test_kernel_windowed_full_window_is_exact():
    """window >= k dispatches to the exact whole-slate kernel."""
    B, D, M, k = 2, 16, 128, 8
    V = make_inputs(23, B, D, M)
    sel_w, _ = dpp_greedy(V, k, interpret=True, window=k)
    sel_e, _ = dpp_greedy(V, k, interpret=True)
    np.testing.assert_array_equal(np.asarray(sel_w), np.asarray(sel_e))


def test_kernel_windowed_unbounded_slate():
    """Slate length beyond the kernel rank: exact eps-stops, windowed
    keeps selecting with O(w M) VMEM state."""
    B, D, M, k, w = 1, 12, 128, 40, 6
    V = make_inputs(29, B, D, M, alpha=1.0)
    sel_e, _ = dpp_greedy(V, k, eps=1e-3, interpret=True)
    sel_w, _ = dpp_greedy(V, k, eps=1e-3, interpret=True, window=w)
    assert int((np.asarray(sel_e) >= 0).sum()) <= D + 3
    s = np.asarray(sel_w)[0]
    assert (s >= 0).all()
    assert len(set(s.tolist())) == k


def test_kernel_windowed_mask_and_padding():
    """Non-aligned M/D + mask through the windowed kernel path."""
    B, D, M, k, w = 2, 19, 200, 18, 5
    V = make_inputs(31, B, D, M)
    rng = np.random.default_rng(1)
    mask = jnp.asarray(rng.uniform(size=(B, M)) > 0.3)
    sel_k, _ = dpp_greedy(V, k, mask=mask, interpret=True, window=w)
    sel_r, _ = dpp_greedy_ref(V, mask, k, window=w)
    np.testing.assert_array_equal(np.asarray(sel_k), np.asarray(sel_r))
    for b in range(B):
        valid = np.asarray(sel_k[b])
        valid = valid[valid >= 0]
        assert np.asarray(mask[b])[valid].all()


def test_kernel_windowed_d_hist_parity_under_eviction():
    """Pins down the windowed d_hist convention against the jnp path on
    slates where eviction changes d2[j].

    Both the kernel and ``dpp_greedy_windowed_lowrank`` record the
    *pre-eviction* marginal ``dj`` (the value the argmax selected on)
    in d_hist, while the row append divides by the *post-eviction*
    ``djp`` — the two differ whenever the evicted pick was correlated
    with j, so equality here is meaningful, not vacuous.
    """
    from repro.core.windowed import dpp_greedy_windowed_lowrank

    B, D, M, k, w = 1, 8, 64, 16, 3
    V = make_inputs(37, B, D, M, alpha=1.0)
    # eviction must actually move the marginals: the same slate scored
    # with a full window differs from the windowed run past step w
    _, dh_exact = dpp_greedy(V, k, interpret=True)
    sel_k, dh_k = dpp_greedy(V, k, interpret=True, window=w)
    assert not np.allclose(
        np.asarray(dh_exact)[0, w:], np.asarray(dh_k)[0, w:], rtol=1e-4
    ), "eviction never changed a marginal — the case is vacuous"
    ref = dpp_greedy_windowed_lowrank(V[0], k, window=w, eps=1e-3)
    np.testing.assert_array_equal(np.asarray(sel_k[0]), np.asarray(ref.indices))
    np.testing.assert_allclose(
        np.asarray(dh_k[0]), np.asarray(ref.d_hist), rtol=3e-4, atol=1e-6
    )
    # d_hist is the selection-time marginal: reselecting each pick against
    # the pre-eviction window reproduces it (kernel side, spot check)
    assert np.asarray(dh_k[0]).min() > 0  # no eps-stop in this regime


def test_kernel_windowed_vmem_budget_uses_window():
    """Resident-mode accounting scales with w, not k: a long slate over
    a big M stays on the resident kernel only because the windowed
    state is (w, M) — the full kernel's (k, M) state dispatches tiled."""
    D, M, k, w = 32, 8192, 512, 8
    assert untiled_vmem_bytes(D, M, k) > VMEM_BUDGET_BYTES
    assert untiled_vmem_bytes(D, M, w) < VMEM_BUDGET_BYTES
    assert plan_tile(D, M, k, windowed=False)[0] == "tiled"
    assert plan_tile(D, M, w, windowed=True) == ("resident", None)
