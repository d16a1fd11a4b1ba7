"""Tiled-vs-resident dpp_greedy kernel parity + the tile plan.

The tiled streaming kernels must select the identical slate (d_hist to
~1 ulp) as the resident whole-in-VMEM kernels and the jnp oracle across
tile sizes {M (single tile), M/2, 128}, ragged tails, masks, eps-stop
and windowed eviction — and a config past the old VMEM gate must run
the Pallas path (interpret mode here) instead of falling back to jnp.
``plan_tile`` is checked over a table of geometries, each cell's shape
among them.

The 8-device sharded tiled-local-update parity runs in a subprocess in
the slow lane (same isolation contract as tests/test_distributed.py).
"""
import functools
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from conftest import assert_greedy_parity, make_greedy_inputs as make_inputs
from repro.core import GreedySpec, GreedySpecError, greedy_map
from repro.kernels.dpp_greedy import (
    VMEM_BUDGET_BYTES,
    dpp_greedy,
    dpp_greedy_ref,
    plan_tile,
    tile_vmem_bytes,
    untiled_vmem_bytes,
)
from repro.kernels.dpp_greedy import tiling
from repro.kernels.dpp_greedy.tiling import LANE, round_up, validate_tile_m

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiles(M):
    """{M (single tile), M/2, 128}, plus 384 (lane-aligned but not a
    power of two) and 640 (one tile wider than M), deduplicated."""
    ts = {M, M // 2, 128, 384, 640}
    return sorted(t for t in ts if t >= 128 and t % 128 == 0)


# ---------------------------------------------------------------------------
# Tiled-vs-resident-vs-oracle parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 6])
@pytest.mark.parametrize("tile", _tiles(512))
def test_tiled_matches_resident_and_ref(window, tile):
    B, D, M, k = 2, 32, 512, 16
    V = make_inputs(B + D + M + k + (window or 0), B, D, M)
    mask = jnp.ones((B, M), bool)
    sel_t, dh_t = dpp_greedy(V, k, window=window, tile_m=tile)
    sel_res, dh_res = dpp_greedy(V, k, window=window)  # resident kernel
    sel_r, dh_r = dpp_greedy_ref(V, mask, k, window=window)
    np.testing.assert_array_equal(np.asarray(sel_t), np.asarray(sel_r))
    np.testing.assert_array_equal(np.asarray(sel_t), np.asarray(sel_res))
    np.testing.assert_allclose(
        np.asarray(dh_t), np.asarray(dh_r), rtol=3e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(dh_t), np.asarray(dh_res), rtol=1e-6, atol=1e-7
    )


@pytest.mark.parametrize("window", [None, 5])
def test_tiled_ragged_tail_and_mask(window):
    """M not a multiple of the tile (padded, masked tail) + a user mask:
    padding can never be selected and the slate matches the oracle."""
    B, D, M, k = 2, 19, 413, 12
    V = make_inputs(17 + (window or 0), B, D, M)
    rng = np.random.default_rng(3)
    mask = jnp.asarray(rng.uniform(size=(B, M)) > 0.3)
    sel_t, dh_t = dpp_greedy(V, k, mask=mask, window=window, tile_m=128)
    sel_r, dh_r = dpp_greedy_ref(V, mask, k, window=window)
    np.testing.assert_array_equal(np.asarray(sel_t), np.asarray(sel_r))
    np.testing.assert_allclose(
        np.asarray(dh_t), np.asarray(dh_r), rtol=3e-4, atol=1e-5
    )
    for b in range(B):
        valid = np.asarray(sel_t[b])
        valid = valid[valid >= 0]
        assert (valid < M).all()
        assert np.asarray(mask[b])[valid].all()


def test_tiled_eps_stop():
    """Rank-deficient kernel: the tiled path stops exactly where the
    oracle stops, and stays stopped across the remaining sweeps."""
    B, D, M, k = 1, 6, 384, 16
    V = make_inputs(13, B, D, M)
    sel_t, dh_t = dpp_greedy(V, k, eps=1e-3, tile_m=128)
    sel_r, dh_r = dpp_greedy_ref(V, jnp.ones((B, M), bool), k, eps=1e-3)
    np.testing.assert_array_equal(np.asarray(sel_t), np.asarray(sel_r))
    assert int((np.asarray(sel_t) >= 0).sum()) <= D + 2


@pytest.mark.parametrize("w", [1, 3])
def test_tiled_windowed_eviction_parity(w):
    """Slates long enough that eviction moves the marginals: the tiled
    windowed kernel pins the same d_hist convention (pre-eviction
    selection marginal) as the jnp windowed path."""
    from repro.core.windowed import dpp_greedy_windowed_lowrank

    B, D, M, k = 1, 8, 256, 16
    V = make_inputs(37, B, D, M, alpha=1.0)
    _, dh_exact = dpp_greedy(V, k, tile_m=128)
    sel_t, dh_t = dpp_greedy(V, k, window=w, tile_m=128)
    assert not np.allclose(
        np.asarray(dh_exact)[0, w:], np.asarray(dh_t)[0, w:], rtol=1e-4
    ), "eviction never changed a marginal — the case is vacuous"
    ref = dpp_greedy_windowed_lowrank(V[0], k, window=w, eps=1e-3)
    np.testing.assert_array_equal(np.asarray(sel_t[0]), np.asarray(ref.indices))
    np.testing.assert_allclose(
        np.asarray(dh_t[0]), np.asarray(ref.d_hist), rtol=3e-4, atol=1e-6
    )
    s = np.asarray(sel_t)[0]
    assert (s >= 0).all() and len(set(s.tolist())) == k  # no eps-stop here


def test_tiled_unbounded_slate():
    """Windowed + tiled: slate length beyond the kernel rank keeps
    selecting with O(w * tile_m) VMEM per grid step."""
    B, D, M, k, w = 1, 12, 256, 40, 6
    V = make_inputs(29, B, D, M, alpha=1.0)
    sel_e, _ = dpp_greedy(V, k, eps=1e-3, tile_m=128)
    sel_w, _ = dpp_greedy(V, k, eps=1e-3, window=w, tile_m=128)
    assert int((np.asarray(sel_e) >= 0).sum()) <= D + 3
    s = np.asarray(sel_w)[0]
    assert (s >= 0).all() and len(set(s.tolist())) == k


@pytest.mark.parametrize("window", [None, 4])
def test_tiled_matches_shared_oracle(greedy_oracle, window):
    """The tiled streaming kernels against the one shared oracle fixture
    (the same ground truth the resident/sharded/streaming suites use)."""
    B, D, M, k = 2, 16, 96, 8
    V = make_inputs(67, B, D, M)
    rng = np.random.default_rng(4)
    mask = jnp.asarray(rng.uniform(size=(B, M)) > 0.25)
    sel, dh = dpp_greedy(V, k, mask=mask, window=window, tile_m=128)
    assert_greedy_parity(greedy_oracle, sel, dh, V, k, window=window,
                         eps=1e-3, mask=mask)


# ---------------------------------------------------------------------------
# Interpret-mode gaps (ROADMAP): the revisited-output running argmax
# under adversarial ties, and the vmap-of-pallas_call batching the
# sharded tiled local update leans on
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 3])
@pytest.mark.parametrize("chunked", [False, True])
def test_tiled_running_argmax_adversarial_ties(window, chunked):
    """Every candidate's marginal is *exactly* float-equal to a twin in
    the other tile (the second tile duplicates the first), so every
    step's running argmax across the revisited (1, 1) cells is decided
    purely by tie-breaking — it must keep the earlier (lower-index)
    candidate, matching jnp.argmax over the concatenated axis, on both
    the per-step sweeps and the fused chunk kernels."""
    B, D, M, k = 1, 12, 256, 6  # two 128-tiles; tile 2 = copy of tile 1
    half = make_inputs(71, B, D, M // 2)
    V = jnp.concatenate([half, half], axis=2)
    sel_r, dh_r = dpp_greedy_ref(V, jnp.ones((B, M), bool), k,
                                 window=window)
    if chunked:
        from repro.kernels.dpp_greedy import (
            dpp_greedy_stream_chunk,
            dpp_greedy_stream_init,
        )

        state = dpp_greedy_stream_init(V, k, window=window, tile_m=128)
        sels = []
        for c in (2, 2, 2):
            state, sel, _ = dpp_greedy_stream_chunk(V, state, c, tile_m=128)
            sels.append(np.asarray(sel))
        sel_t = np.concatenate(sels, axis=1)
    else:
        sel_t, _ = dpp_greedy(V, k, window=window, tile_m=128)
    np.testing.assert_array_equal(np.asarray(sel_t), np.asarray(sel_r))
    # the ties were real and broke low: a twin pair stays exactly tied
    # until one member is selected, so a pick from the higher tile is
    # only legitimate when it is the twin of an earlier pick whose
    # eviction repaired its d2 (windowed only) — any other high-tile
    # pick means the running argmax broke a live tie the wrong way
    s = np.asarray(sel_t)[0]
    assert (s >= 0).all()
    prev = set()
    for x in s.tolist():
        if x >= M // 2:
            assert window is not None and (x - M // 2) in prev, (
                f"tie broke toward the higher tile at {x}"
            )
        prev.add(x)
    assert (s[: min(len(s), 2)] < M // 2).all()  # fresh ties broke low


@pytest.mark.parametrize("window", [None, 3])
def test_vmap_of_tiled_update_matches_per_problem(window):
    """The batched sharded path vmaps the per-device SPMD body, so the
    per-step tile kernels run under vmap-of-pallas_call.  Pin that
    batching rule directly: vmapping the shard-local update equals
    running it per problem."""
    from repro.kernels.dpp_greedy.tiled import (
        eviction_coeffs,
        tiled_update_exact,
        tiled_update_windowed,
    )

    B, D, M, k = 3, 8, 256, 5
    rng = np.random.default_rng(73)
    V = make_inputs(73, B, D, M)
    d2 = jnp.sum(V * V, axis=1)
    j = jnp.asarray(rng.integers(0, M, size=B), jnp.int32)
    dj = jnp.sqrt(jnp.take_along_axis(d2, j[:, None], 1))[:, 0]
    vj = jnp.take_along_axis(V, j[:, None, None], axis=2)[:, :, 0]
    stopped = jnp.zeros((B,), bool)
    base = jnp.zeros((B,), jnp.int32)
    if window is None:
        C = jnp.asarray(rng.normal(size=(B, k, M)), jnp.float32) * 0.1
        cj = jnp.take_along_axis(C, j[:, None, None], axis=2)[:, :, 0]
        fn = lambda Vb, Cb, d2b, vjb, cjb, djb, st, jb, bb: (
            tiled_update_exact(Vb, Cb, d2b, vjb, cjb, djb, st, jb, bb,
                               tile_m=128)
        )
        batched = jax.vmap(fn)(V, C, d2, vj, cj, dj, stopped, j, base)
        single = [fn(V[b], C[b], d2[b], vj[b], cj[b], dj[b], stopped[b],
                     j[b], base[b]) for b in range(B)]
    else:
        w = window
        C = jnp.asarray(rng.normal(size=(B, w, M)), jnp.float32) * 0.1
        win = jnp.asarray(rng.integers(0, M, size=(B, w)), jnp.int32)
        cj = jnp.take_along_axis(C, j[:, None, None], axis=2)[:, :, 0]
        Cw = jnp.take_along_axis(C, jnp.clip(win, 0)[:, None, :], axis=2)
        full = jnp.ones((B,), bool)
        cos, sin, cj_post, d2j = eviction_coeffs(Cw, cj, dj * dj, full, w)
        djp = jnp.sqrt(jnp.maximum(d2j, 1e-12))
        pos = jnp.full((B,), w - 1, jnp.int32)
        fn = lambda Vb, Cb, d2b, vjb, cjb, djb, st, fl, co, si, jb, bb, po: (
            tiled_update_windowed(Vb, Cb, d2b, vjb, cjb, djb, st, fl, co,
                                  si, jb, bb, po, w=w, tile_m=128)
        )
        batched = jax.vmap(fn)(V, C, d2, vj, cj_post, djp, stopped, full,
                               cos, sin, j, base, pos)
        single = [fn(V[b], C[b], d2[b], vj[b], cj_post[b], djp[b],
                     stopped[b], full[b], cos[b], sin[b], j[b], base[b],
                     pos[b]) for b in range(B)]
    for out_b, outs in zip(batched, zip(*single)):
        np.testing.assert_allclose(
            np.asarray(out_b), np.stack([np.asarray(o) for o in outs]),
            rtol=1e-6, atol=1e-7,
        )


# ---------------------------------------------------------------------------
# The acceptance bar: past the old VMEM gate, the kernel path runs
# ---------------------------------------------------------------------------


def test_past_gate_runs_kernel_and_matches_oracle():
    """D=64, M=131072, w=8 exceeds the whole-array VMEM budget — the old
    gate silently fell back to jnp here.  plan_tile must now dispatch
    the tiled Pallas kernels (interpret mode on CPU) and the slate must
    be identical to the jnp oracle.  k > w so the *windowed* tiled
    kernel (eviction included) is the one exercised past the gate."""
    B, D, M, k, w = 1, 64, 131072, 16, 8
    assert untiled_vmem_bytes(D, M, w) > VMEM_BUDGET_BYTES
    mode, tm = plan_tile(D, M, w, windowed=True)
    assert mode == "tiled" and tm is not None
    assert tile_vmem_bytes(D, tm, w, windowed=True) <= VMEM_BUDGET_BYTES
    V = make_inputs(19, B, D, M)
    sel_t, dh_t = dpp_greedy(V, k, window=w, eps=1e-6, interpret=True)
    sel_r, dh_r = dpp_greedy_ref(V, jnp.ones((B, M), bool), k, window=w,
                                 eps=1e-6)
    np.testing.assert_array_equal(np.asarray(sel_t), np.asarray(sel_r))
    np.testing.assert_allclose(
        np.asarray(dh_t), np.asarray(dh_r), rtol=3e-4, atol=1e-5
    )


# ---------------------------------------------------------------------------
# The tile plan / dispatch plumbing
# ---------------------------------------------------------------------------


def test_tile_policy_decides():
    # comfortably in budget -> resident
    assert plan_tile(64, 4096, 16, False) == ("resident", None)
    # past budget -> tiled with a fitting, lane-aligned tile
    mode, tm = plan_tile(64, 1 << 20, 16, False)
    assert mode == "tiled" and tm % 128 == 0
    assert tile_vmem_bytes(64, tm, 16, False) <= VMEM_BUDGET_BYTES
    # explicit tile_m forces tiling even when resident would fit
    assert plan_tile(16, 256, 4, False, tile_m=128) == ("tiled", 128)
    # pathological row count: even one lane tile exceeds the budget
    assert plan_tile(200_000, 1 << 20, 16, False) == ("jnp", None)


def test_tile_policy_validation():
    with pytest.raises(ValueError, match="tile_m"):
        validate_tile_m(100)
    with pytest.raises(ValueError, match="tile_m"):
        plan_tile(16, 256, 4, False, tile_m=100)
    with pytest.raises(ValueError, match="tile_m"):
        plan_tile(16, 256, 4, False, tile_m=-128)


# (D, M, state_rows, windowed, chunked, tile_m) -> (mode, tile).  The
# expected tiles are the VMEM model's at the module budget.
_PLANS = {
    "feed1k": ((100, 1000, 50, False, False, None), ("resident", None)),
    "pool1m": ((32, 1_000_000, 20, False, False, None), ("tiled", 19584)),
    "pool1m-4chip-shard": (
        (32, 250_000, 20, False, False, None), ("tiled", 19584)),
    "pool1m-4chip-explicit": (
        (32, 250_000, 20, False, False, 2048), ("tiled", 2048)),
    "pool1m-chunked": ((32, 1_000_000, 20, False, True, None),
                       ("tiled", 16256)),
    "resident-edge": ((32, 49_152, 20, False, False, None),
                      ("resident", None)),
    "past-resident-edge": ((32, 49_153, 20, False, False, None),
                           ("tiled", 19584)),
    "windowed": ((64, 131_072, 8, True, False, None), ("tiled", 16256)),
    "windowed-chunked": ((64, 131_072, 8, True, True, None),
                         ("tiled", 16256)),
    "wide-state": ((64, 1 << 20, 32, False, False, None), ("tiled", 13056)),
    "narrow-D": ((8, 1 << 22, 1, False, False, None), ("tiled", 39296)),
    "wide-D": ((256, 1 << 22, 50, False, False, None), ("tiled", 4608)),
    "wide-D-windowed-chunked": ((256, 1 << 22, 50, True, True, None),
                                ("tiled", 3968)),
    "huge-D-short-pool": ((4096, 1000, 20, False, False, None),
                          ("tiled", 256)),
    "huge-D-short-pool-chunked": ((4096, 1000, 20, False, True, None),
                                  ("tiled", 256)),
    "long-window": ((1000, 100_000, 50, True, False, None), ("tiled", 1280)),
    "small-shard": ((16, 500, 12, False, False, None), ("resident", None)),
    "explicit-over-resident": ((16, 500, 12, False, False, 128),
                               ("tiled", 128)),
    "pathological-D": ((200_000, 1 << 20, 16, False, False, None),
                       ("jnp", None)),
    "pathological-D-windowed": ((100_000, 1 << 20, 16, True, False, None),
                                ("jnp", None)),
}


@pytest.mark.parametrize("name", sorted(_PLANS))
def test_plan_tile(name):
    """Each geometry plans the expected mode and tile.  A resident plan
    is a working set within the budget; a planned tile fits the budget
    and is the widest LANE multiple that does (short of the caps); an
    explicit tile is taken as given; jnp means one lane-width tile
    already overflows."""
    (D, M, R, windowed, chunked, tile_m), want = _PLANS[name]
    got = plan_tile(D, M, R, windowed, chunked, tile_m)
    assert got == want
    mode, tm = got
    untiled = untiled_vmem_bytes(D, M, R)
    per_tile = functools.partial(tile_vmem_bytes, D, state_rows=R,
                                 windowed=windowed, chunked=chunked)
    if tile_m is not None:
        return
    if mode == "resident":
        assert untiled <= VMEM_BUDGET_BYTES
        return
    assert untiled > VMEM_BUDGET_BYTES
    if mode == "jnp":
        assert per_tile(tile_m=LANE) > VMEM_BUDGET_BYTES
        return
    assert tm % LANE == 0 and per_tile(tile_m=tm) <= VMEM_BUDGET_BYTES
    assert tm == min(tiling.MAX_AUTO_TILE, round_up(M, LANE)) \
        or per_tile(tile_m=tm + LANE) > VMEM_BUDGET_BYTES


def test_plan_tile_caps_at_max_auto_tile(monkeypatch):
    """At the module budget no geometry reaches ``MAX_AUTO_TILE`` (the
    narrowest streams allow ~39k lanes); under a budget large enough to
    allow more, the planned tile stops at the cap."""
    monkeypatch.setattr(tiling, "VMEM_BUDGET_BYTES", 1 << 30)
    assert plan_tile(8, 1 << 24, 1, False) == ("tiled", tiling.MAX_AUTO_TILE)


def _reject_spec():
    GreedySpec(k=4, backend="pallas", tile_m="auto")


def _reject_config():
    from repro.serving.reranker import DPPRerankConfig

    DPPRerankConfig(use_kernel=True, tile_m="auto")


def _reject_kernel():
    dpp_greedy(jnp.ones((1, 4, 128)), 2, tile_m="auto")


def _reject_sharded():
    from repro.core.sharded import dpp_greedy_sharded

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    dpp_greedy_sharded(jnp.ones((4, 128)), 2, mesh=mesh, tile_m="auto")


@pytest.mark.parametrize("call", [
    _reject_spec, _reject_config, _reject_kernel, _reject_sharded,
    lambda: validate_tile_m("auto"),
], ids=["GreedySpec", "DPPRerankConfig", "dpp_greedy",
        "dpp_greedy_sharded", "validate_tile_m"])
def test_tile_m_auto_is_rejected(call):
    """A tile is an int or None: the string the removed measured-cache
    mode took is malformed input wherever a tile is accepted."""
    with pytest.raises(ValueError, match="tile_m"):
        call()


# the process-wide tile override that earlier revisions read, spelled in
# two parts so a search for the removed name finds only history
_FORMER_OVERRIDE = "DPP_" + "TILE_M"


@pytest.mark.parametrize("value", ["128", "2048", "auto"])
def test_former_tile_override_is_ignored(monkeypatch, value):
    """The environment no longer sets the tile: each cell's shape plans
    and dispatches the model's tile whatever the former override says."""
    from repro import obs
    from repro.kernels.dpp_greedy.ops import dpp_greedy_plan

    monkeypatch.setenv(_FORMER_OVERRIDE, value)
    obs.disable()
    with obs.session(obs.ObsConfig(enabled=True)) as sess:
        assert dpp_greedy_plan(100, 1000, 50)[:2] == ("resident", None)
        assert sess.registry.get("dpp_tile_m").value() == 0
        assert dpp_greedy_plan(32, 1_000_000, 20)[:2] == ("tiled", 19584)
        assert sess.registry.get("dpp_tile_m").value() == 19584
        snap = sess.registry.snapshot()
    assert snap["counters"]["dpp_tile_source_total"] == {"source=model": 2}


def test_vmem_bytes_shim_removed():
    # The pre-tiling ``vmem_bytes`` name shipped as a DeprecationWarning
    # shim for one release after PR 4; it is gone now everywhere it was
    # re-exported.  ``untiled_vmem_bytes`` is the resident-mode model.
    import importlib

    # (``import ... as pkg`` would grab the ``dpp_greedy`` *function*
    # re-exported by repro.kernels — go through importlib instead)
    pkg = importlib.import_module("repro.kernels.dpp_greedy")
    from repro.kernels.dpp_greedy import ops, tiling

    for mod in (pkg, ops, tiling):
        assert not hasattr(mod, "vmem_bytes")
    assert "vmem_bytes" not in pkg.__all__


def test_greedy_spec_tile_m_validation_and_threading():
    with pytest.raises(GreedySpecError, match="tile_m"):
        GreedySpec(k=4, tile_m=100)
    with pytest.raises(GreedySpecError, match="tile_m"):
        GreedySpec(k=4, backend="jnp", tile_m=128)
    # backend='auto' without a mesh resolves to jnp, which would also
    # silently ignore the tile — rejected at construction
    with pytest.raises(GreedySpecError, match="tile_m"):
        GreedySpec(k=4, tile_m=128)
    V = make_inputs(41, 1, 16, 384)[0]
    ref = greedy_map(GreedySpec(k=8, backend="jnp", eps=1e-6), V=V)
    got = greedy_map(
        GreedySpec(k=8, backend="pallas", eps=1e-6, tile_m=128), V=V
    )
    np.testing.assert_array_equal(np.asarray(ref.indices),
                                  np.asarray(got.indices))


def test_rerank_config_tile_m():
    from repro.serving.reranker import DPPRerankConfig
    from conftest import serve_rerank

    with pytest.raises(ValueError, match="tile_m"):
        DPPRerankConfig(tile_m=100, use_kernel=True)
    with pytest.raises(ValueError, match="tile_m"):
        DPPRerankConfig(tile_m=128)  # jnp backend would ignore it
    rng = np.random.default_rng(43)
    M, D = 400, 16
    scores = jnp.asarray(rng.uniform(size=M), jnp.float32)
    feats = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    feats = feats / jnp.linalg.norm(feats, axis=1, keepdims=True)
    kw = dict(slate_size=10, shortlist=256, eps=1e-6)
    base, _ = serve_rerank(scores, feats, DPPRerankConfig(**kw))
    tiled, _ = serve_rerank(
        scores, feats, DPPRerankConfig(use_kernel=True, tile_m=128, **kw)
    )
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tiled))


# ---------------------------------------------------------------------------
# Sharded local update through the tiled kernel (fast: 1-device mesh)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 4])
def test_sharded_tiled_local_update_one_device(window):
    from repro.core import dpp_greedy_lowrank, dpp_greedy_sharded
    from repro.core.windowed import dpp_greedy_windowed_lowrank

    rng = np.random.default_rng(45)
    M, D, k = 300, 24, 12
    V = jnp.asarray(rng.normal(size=(D, M)), jnp.float32) / np.sqrt(D)
    mask = jnp.asarray(rng.uniform(size=M) > 0.3)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    if window is None:
        ref = dpp_greedy_lowrank(V, k, eps=1e-6, mask=mask)
    else:
        ref = dpp_greedy_windowed_lowrank(V, k, window=window, eps=1e-6,
                                          mask=mask)
    got = dpp_greedy_sharded(
        V, k, mesh=mesh, window=window, eps=1e-6, mask=mask, tile_m=128
    )
    np.testing.assert_array_equal(np.asarray(ref.indices),
                                  np.asarray(got.indices))
    np.testing.assert_allclose(np.asarray(ref.d_hist), np.asarray(got.d_hist),
                               rtol=1e-6, atol=1e-7)


def test_sharded_tiled_batched_one_device():
    """The batched sharded path vmaps the SPMD body — the tiled Pallas
    pass inside must batch correctly (vmap-of-pallas_call)."""
    from repro.core import dpp_greedy_lowrank_batch, dpp_greedy_sharded

    rng = np.random.default_rng(46)
    B, D, M, k = 3, 12, 200, 8
    V = jnp.asarray(rng.normal(size=(B, D, M)), jnp.float32) / np.sqrt(D)
    mask = jnp.asarray(rng.uniform(size=(B, M)) > 0.3)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    ref = dpp_greedy_lowrank_batch(V, k, 1e-6, mask)
    got = dpp_greedy_sharded(V, k, mesh=mesh, eps=1e-6, mask=mask, tile_m=128)
    np.testing.assert_array_equal(np.asarray(ref.indices),
                                  np.asarray(got.indices))


# ---------------------------------------------------------------------------
# Sharded tiled local update, 8 devices (subprocess, slow lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_tiled_multidevice_parity():
    """On an 8-host-device mesh, the sharded path with tile_m set (every
    device's local update streamed through the tiled Pallas pass) selects
    the identical slate as the single-device low-rank paths — exact and
    windowed, ragged M (padded to P * tile_m), masked, batched."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import numpy as np, jax, jax.numpy as jnp
            from repro.core import (dpp_greedy_sharded, dpp_greedy_lowrank,
                                    dpp_greedy_lowrank_batch)
            from repro.core.windowed import dpp_greedy_windowed_lowrank
            assert jax.device_count() == 8
            import jax
            from jax.sharding import AxisType
            mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
            rng = np.random.default_rng(0)
            M, D, k = 3001, 16, 12  # not divisible by 8*128 (padded shards)
            V = jnp.asarray(rng.normal(size=(D, M)), jnp.float32) / np.sqrt(D)
            mask = jnp.asarray(rng.uniform(size=M) > 0.2)
            for window in (None, 1, 5):
                for m in (None, mask):
                    if window is None:
                        ref = dpp_greedy_lowrank(V, k, eps=1e-6, mask=m)
                    else:
                        ref = dpp_greedy_windowed_lowrank(
                            V, k, window=window, eps=1e-6, mask=m)
                    got = dpp_greedy_sharded(
                        V, k, mesh=mesh, window=window, eps=1e-6, mask=m,
                        tile_m=128)
                    np.testing.assert_array_equal(
                        np.asarray(ref.indices), np.asarray(got.indices))
                    np.testing.assert_allclose(
                        np.asarray(ref.d_hist), np.asarray(got.d_hist),
                        rtol=1e-6, atol=1e-7)
            B = 3
            Vb = jnp.asarray(rng.normal(size=(B, D, M)), jnp.float32)
            Vb = Vb / np.sqrt(D)
            mb = jnp.asarray(rng.uniform(size=(B, M)) > 0.3)
            ref = dpp_greedy_lowrank_batch(Vb, 8, 1e-6, mb)
            got = dpp_greedy_sharded(Vb, 8, mesh=mesh, eps=1e-6, mask=mb,
                                     tile_m=128)
            np.testing.assert_array_equal(
                np.asarray(ref.indices), np.asarray(got.indices))
            print("SHARDED-TILED-OK")
        """)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED-TILED-OK" in out.stdout
