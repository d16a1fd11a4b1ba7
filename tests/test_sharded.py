"""Sharded candidate-axis greedy MAP (core.sharded + serving.sharded_rerank).

Fast lane: GreedySpec construction-time validation, mask threading
through the serving layer, and the full sharded code path on a trivial
1-device mesh (the collectives run with axis size 1, so every branch is
exercised in-process).

Slow lane: multi-device correctness runs in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test
process keeps 1 device, per the dry-run isolation contract).  The
hypothesis property under test is the subsystem's core guarantee:
sharded greedy — exact and windowed, padded and masked — selects the
bit-identical slate and d_hist as the single-device low-rank path on
the gathered V.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from conftest import assert_greedy_parity, make_greedy_inputs, serve_rerank
from repro.core import (
    GreedySpec,
    GreedySpecError,
    dpp_greedy_lowrank,
    dpp_greedy_sharded,
    greedy_map,
    sharded_topk,
)
from repro.core.windowed import dpp_greedy_windowed_lowrank
from repro.serving import DPPRerankConfig, Reranker, RerankRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _problem(seed, M=120, D=24):
    # the shared conftest builder (alpha=None = this suite's historical
    # gaussian / sqrt(D) conditioning)
    return make_greedy_inputs(seed, None, D, M, alpha=None)


# ---------------------------------------------------------------------------
# GreedySpec construction-time validation
# ---------------------------------------------------------------------------


def test_spec_validation_at_construction():
    """Bad configs fail with a named error when the spec is built, not
    deep inside a jitted trace."""
    with pytest.raises(GreedySpecError, match="k must be"):
        GreedySpec(k=0)
    with pytest.raises(GreedySpecError, match="k must be"):
        GreedySpec(k=-3)
    with pytest.raises(GreedySpecError, match="window must be"):
        GreedySpec(k=5, window=0)
    with pytest.raises(GreedySpecError, match="window must be"):
        GreedySpec(k=5, window=-1)
    with pytest.raises(GreedySpecError, match="unknown backend"):
        GreedySpec(k=5, backend="tpu")
    with pytest.raises(GreedySpecError, match="mesh"):
        GreedySpec(k=5, backend="sharded")
    with pytest.raises(GreedySpecError, match="mesh"):
        GreedySpec(k=5, backend="pallas", mesh=jax.make_mesh(
            (1,), ("data",), axis_types=(AxisType.Auto,)))
    with pytest.raises(GreedySpecError, match="silently ignored"):
        GreedySpec(k=5, backend="jnp", mesh=jax.make_mesh(
            (1,), ("data",), axis_types=(AxisType.Auto,)))
    # GreedySpecError is a ValueError: existing except-ValueError callers hold
    assert issubclass(GreedySpecError, ValueError)
    # valid specs still construct
    GreedySpec(k=5, window=5)
    GreedySpec(k=5, backend="sharded", mesh=jax.make_mesh(
        (1,), ("data",), axis_types=(AxisType.Auto,)))


def test_rerank_config_validation():
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    with pytest.raises(ValueError, match="mutually exclusive"):
        DPPRerankConfig(use_kernel=True, mesh=mesh)
    spec = DPPRerankConfig(slate_size=4, mesh=mesh).greedy_spec()
    assert spec.backend == "sharded" and spec.mesh is mesh


def test_rerank_config_validates_at_construction():
    """Nonsensical slate/shortlist/window/eps fail when the config is
    built (mirroring GreedySpecError), not as shape/trace errors inside
    the jitted serve step."""
    with pytest.raises(ValueError, match="slate_size must be"):
        DPPRerankConfig(slate_size=0)
    with pytest.raises(ValueError, match="slate_size must be"):
        DPPRerankConfig(slate_size=-5)
    with pytest.raises(ValueError, match="shortlist must be"):
        DPPRerankConfig(shortlist=0)
    with pytest.raises(ValueError, match="shortlist must be"):
        DPPRerankConfig(shortlist=-1)
    with pytest.raises(ValueError, match="window must be"):
        DPPRerankConfig(window=0)
    with pytest.raises(ValueError, match="window must be"):
        DPPRerankConfig(window=-2)
    with pytest.raises(ValueError, match="eps must be"):
        DPPRerankConfig(eps=-1e-6)
    # boundary values that must still construct
    DPPRerankConfig(slate_size=1, shortlist=1, window=1, eps=0.0)


# ---------------------------------------------------------------------------
# Sharded greedy on a 1-device mesh (full code path, in-process)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_sharded_matches_lowrank_one_device(seed):
    V = _problem(seed)
    ref = dpp_greedy_lowrank(V, 10, eps=1e-6)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    got = dpp_greedy_sharded(V, 10, mesh=mesh, eps=1e-6)
    np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(got.indices))
    np.testing.assert_array_equal(np.asarray(ref.d_hist), np.asarray(got.d_hist))
    assert int(ref.n_selected) == int(got.n_selected)


@pytest.mark.parametrize("window", [None, 5])
def test_sharded_matches_shared_oracle(greedy_oracle, window):
    """The sharded backend against the one shared oracle fixture — the
    same ground truth the kernel and streaming suites assert against."""
    V = _problem(7)
    rng = np.random.default_rng(7)
    mask = jnp.asarray(rng.uniform(size=V.shape[1]) > 0.25)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    got = dpp_greedy_sharded(
        V, 10, mesh=mesh, window=window, eps=1e-6, mask=mask,
    )
    assert_greedy_parity(greedy_oracle, got.indices, got.d_hist, V, 10,
                         window=window, eps=1e-6, mask=mask)


def test_sharded_windowed_matches_one_device():
    V = _problem(3)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    ref = dpp_greedy_windowed_lowrank(V, 24, window=5, eps=1e-6)
    got = dpp_greedy_sharded(V, 24, mesh=mesh, window=5, eps=1e-6)
    np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(got.indices))
    np.testing.assert_array_equal(np.asarray(ref.d_hist), np.asarray(got.d_hist))


def test_sharded_mask_and_dispatch():
    """greedy_map routes backend='sharded' (and auto + mesh) correctly;
    masked candidates never selected."""
    V = _problem(4)
    M = V.shape[1]
    rng = np.random.default_rng(4)
    mask = jnp.asarray(rng.uniform(size=M) > 0.4)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    ref = dpp_greedy_lowrank(V, 8, eps=1e-6, mask=mask)
    got = greedy_map(
        GreedySpec(k=8, backend="sharded", mesh=mesh, eps=1e-6), V=V, mask=mask
    )
    np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(got.indices))
    auto = greedy_map(GreedySpec(k=8, mesh=mesh, eps=1e-6), V=V, mask=mask)
    np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(auto.indices))
    sel = np.asarray(got.indices)
    assert all(bool(mask[i]) for i in sel if i >= 0)


def test_sharded_rejects_dense_and_bad_rank():
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    spec = GreedySpec(k=4, backend="sharded", mesh=mesh)
    L = jnp.eye(8)
    with pytest.raises(ValueError, match="low-rank V"):
        greedy_map(spec, L=L)
    with pytest.raises(ValueError, match="ndim"):
        dpp_greedy_sharded(jnp.ones((2, 2, 4, 16)), 2, mesh=mesh)
    with pytest.raises(ValueError, match="mesh has no axis"):
        dpp_greedy_sharded(jnp.ones((4, 16)), 2, mesh=mesh, axis_name="model")


def test_sharded_topk_one_device():
    rng = np.random.default_rng(7)
    s = jnp.asarray(rng.uniform(size=97), jnp.float32)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    v1, i1 = jax.lax.top_k(s, 13)
    v2, i2 = sharded_topk(s, 13, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_sharded_rerank_matches_dense_one_device():
    rng = np.random.default_rng(9)
    M, D = 300, 16
    scores = jnp.asarray(rng.uniform(size=M), jnp.float32)
    feats = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    feats = feats / jnp.linalg.norm(feats, axis=1, keepdims=True)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    for window in (None, 4):
        dense, _ = serve_rerank(
            scores, feats,
            DPPRerankConfig(slate_size=10, shortlist=128, alpha=3.0,
                            eps=1e-6, window=window),
        )
        sh, _ = serve_rerank(
            scores, feats,
            DPPRerankConfig(slate_size=10, shortlist=128, alpha=3.0,
                            eps=1e-6, window=window, mesh=mesh),
        )
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(sh))


# ---------------------------------------------------------------------------
# Batched sharded greedy / rerank (users x candidates on one mesh)
# ---------------------------------------------------------------------------


def test_sharded_batched_matches_lowrank_batch_one_device():
    """V (B, D, M): the batched sharded loop (state (B, Mloc) per device,
    collectives batched over B) matches the vmap single-device path."""
    from repro.core import dpp_greedy_lowrank_batch

    rng = np.random.default_rng(21)
    B, D, M, k = 4, 12, 90, 8
    V = jnp.asarray(rng.normal(size=(B, D, M)), jnp.float32) / np.sqrt(D)
    mask = jnp.asarray(rng.uniform(size=(B, M)) > 0.3)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    ref = dpp_greedy_lowrank_batch(V, k, 1e-6, mask)
    got = dpp_greedy_sharded(V, k, mesh=mesh, eps=1e-6, mask=mask)
    assert got.indices.shape == (B, k)
    np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(got.indices))
    np.testing.assert_allclose(
        np.asarray(ref.d_hist), np.asarray(got.d_hist), rtol=1e-6, atol=1e-7
    )
    np.testing.assert_array_equal(
        np.asarray(ref.n_selected), np.asarray(got.n_selected)
    )
    # dispatch no longer rejects batched V on the sharded backend
    via_map = greedy_map(
        GreedySpec(k=k, backend="sharded", mesh=mesh, eps=1e-6), V=V, mask=mask
    )
    np.testing.assert_array_equal(
        np.asarray(ref.indices), np.asarray(via_map.indices)
    )


def test_sharded_topk_batched_one_device():
    rng = np.random.default_rng(22)
    s = jnp.asarray(rng.uniform(size=(3, 97)), jnp.float32)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    v1, i1 = jax.lax.top_k(s, 13)  # top_k batches over leading axes
    v2, i2 = sharded_topk(s, 13, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


@pytest.mark.parametrize("window", [None, 3])
@pytest.mark.parametrize("per_user_feats", [False, True])
def test_rerank_batch_sharded_matches_vmap_one_device(window, per_user_feats):
    """A batched request with cfg.mesh: identical slates, per user, to
    the vmap of the single-device dispatch — shared or per-user
    features, per-user masks, padded M (not divisible by the axis
    size)."""
    rng = np.random.default_rng(23)
    B, M, D = 4, 121, 8
    scores = jnp.asarray(rng.uniform(size=(B, M)), jnp.float32)
    shape = (B, M, D) if per_user_feats else (M, D)
    feats = rng.normal(size=shape).astype(np.float32)
    feats /= np.linalg.norm(feats, axis=-1, keepdims=True)
    feats = jnp.asarray(feats)
    mask = jnp.asarray(rng.uniform(size=(B, M)) > 0.25)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    kw = dict(slate_size=6, shortlist=64, alpha=3.0, eps=1e-6, window=window)
    ref, ref_dh = serve_rerank(scores, feats, DPPRerankConfig(**kw), mask=mask)
    got, got_dh = serve_rerank(
        scores, feats, DPPRerankConfig(mesh=mesh, **kw), mask=mask
    )
    assert got.shape == (B, 6)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    np.testing.assert_allclose(
        np.asarray(ref_dh), np.asarray(got_dh), rtol=1e-6, atol=1e-7
    )


def test_rerank_batch_sharded_eps_stop():
    """Rank-deficient per-user kernels eps-stop at the same step as the
    vmap single-device path (slots after the stop hold -1)."""
    rng = np.random.default_rng(24)
    B, M, D = 4, 80, 3
    scores = jnp.asarray(rng.uniform(size=(B, M)), jnp.float32)
    feats = rng.normal(size=(B, M, D)).astype(np.float32)
    feats /= np.linalg.norm(feats, axis=-1, keepdims=True)
    feats = jnp.asarray(feats)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    kw = dict(slate_size=10, shortlist=64, alpha=2.0, eps=1e-2)
    ref, _ = serve_rerank(scores, feats, DPPRerankConfig(**kw))
    got, _ = serve_rerank(scores, feats, DPPRerankConfig(mesh=mesh, **kw))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    assert (np.asarray(got) == -1).any()  # the stop actually fired


# ---------------------------------------------------------------------------
# Mask plumbing regressions (shared (M,) mask x batched V; poisoned
# scores on masked items)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "pallas", "sharded"])
def test_shared_mask_batched_V_all_backends(backend):
    """A shared (M,) mask alongside a batched V (B, D, M) is broadcast to
    (B, M) in dispatch — regression for the pallas path leaving mb
    unbatched (mask.reshape(B, 1, M) blew up) and for the jnp/sharded
    batch paths vmapping a rank-1 mask."""
    rng = np.random.default_rng(31)
    B, D, M, k = 3, 10, 72, 6
    V = jnp.asarray(rng.normal(size=(B, D, M)), jnp.float32) / np.sqrt(D)
    mask = jnp.asarray(rng.uniform(size=M) > 0.4)  # shared across users
    kw = dict(k=k, eps=1e-6)
    if backend == "sharded":
        kw["mesh"] = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    spec = GreedySpec(backend=backend, **kw)
    got = greedy_map(spec, V=V, mask=mask)
    ref = greedy_map(
        GreedySpec(k=k, backend="jnp", eps=1e-6),
        V=V,
        mask=jnp.broadcast_to(mask, (B, M)),
    )
    assert got.indices.shape == (B, k)
    np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(got.indices))
    sel = np.asarray(got.indices)
    assert all(bool(mask[i]) for i in sel.ravel() if i >= 0)


@pytest.mark.parametrize("poison", [float("nan"), float("-inf")])
def test_sharded_rerank_masked_score_poison(poison):
    """A NaN/-inf score on a *masked* item must not leak into the kernel:
    V's masked columns are zeroed exactly as the single-device rerank
    zeroes masked shortlist relevances."""
    rng = np.random.default_rng(32)
    M, D = 150, 8
    scores = rng.uniform(size=M).astype(np.float32)
    feats = rng.normal(size=(M, D)).astype(np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True)
    mask = np.ones(M, bool)
    mask[7] = False
    clean = jnp.asarray(scores)
    scores = scores.copy()
    scores[7] = poison
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    cfg = DPPRerankConfig(
        slate_size=8, shortlist=64, alpha=3.0, eps=1e-6, mesh=mesh
    )
    slate, dh = serve_rerank(jnp.asarray(scores), jnp.asarray(feats), cfg,
                             mask=jnp.asarray(mask))
    slate, dh = np.asarray(slate), np.asarray(dh)
    assert (slate >= 0).sum() == 8 and 7 not in slate.tolist()
    assert np.isfinite(dh).all()
    # the poisoned-but-masked score changes nothing vs a clean one
    ref, _ = serve_rerank(clean, jnp.asarray(feats), cfg,
                          mask=jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(ref), slate)


def test_sharded_rerank_rejects_rank_inconsistent_inputs():
    """Rank-inconsistent inputs must never reach the mesh: a request
    whose feats or mask carry a batch axis the scores lack fails at
    RerankRequest construction, and a batched request cannot stream."""
    rng = np.random.default_rng(34)
    M, D, B = 64, 6, 3
    scores = jnp.asarray(rng.uniform(size=M), jnp.float32)
    feats = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    cfg = DPPRerankConfig(slate_size=4, shortlist=32, mesh=mesh)
    with pytest.raises(ValueError, match="feats must be"):
        RerankRequest(scores=scores, feats=jnp.stack([feats] * B))
    with pytest.raises(ValueError, match="mask must be"):
        RerankRequest(scores=scores, feats=feats,
                      mask=jnp.ones((B, M), bool))
    with pytest.raises(ValueError, match="single request"):
        Reranker(cfg).stream(
            RerankRequest(scores=jnp.stack([scores] * B), feats=feats)
        )


def test_sharded_rerank_inf_relevance_outside_shortlist():
    """An unmasked item whose relevance overflows to inf (alpha < 1 with
    a very negative score) ranks outside the top-C shortlist — the
    single-device rerank never builds its V column, and the sharded path
    must likewise zero it rather than let the inf poison the matvec."""
    rng = np.random.default_rng(33)
    M, D = 200, 8
    scores = rng.uniform(size=M).astype(np.float32)
    scores[11] = -130.0  # 0.5 ** -130 overflows float32 -> inf relevance
    feats = rng.normal(size=(M, D)).astype(np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    kw = dict(slate_size=8, shortlist=64, alpha=0.5, eps=1e-6)
    ref, _ = serve_rerank(jnp.asarray(scores), jnp.asarray(feats),
                          DPPRerankConfig(**kw))
    got, dh = serve_rerank(jnp.asarray(scores), jnp.asarray(feats),
                           DPPRerankConfig(mesh=mesh, **kw))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    assert np.isfinite(np.asarray(dh)).all()
    assert 11 not in np.asarray(got).tolist()


# ---------------------------------------------------------------------------
# Mask threading through the serving layer (satellite: serve can now
# exclude already-seen / filtered items)
# ---------------------------------------------------------------------------


def test_rerank_mask_excludes_banned_items():
    rng = np.random.default_rng(11)
    M, D = 200, 16
    scores = jnp.asarray(rng.uniform(size=M), jnp.float32)
    feats = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    feats = feats / jnp.linalg.norm(feats, axis=1, keepdims=True)
    cfg = DPPRerankConfig(slate_size=10, shortlist=64, alpha=3.0, eps=1e-6)
    base, _ = serve_rerank(scores, feats, cfg)
    banned = np.asarray(base)[:5]
    mask = jnp.ones(M, bool).at[banned].set(False)
    slate, _ = serve_rerank(scores, feats, cfg, mask=mask)
    slate = np.asarray(slate)
    assert set(banned.tolist()).isdisjoint(set(slate.tolist()))
    assert (slate >= 0).sum() == 10  # the slate refills from unbanned items


def test_rerank_batch_mask():
    rng = np.random.default_rng(12)
    B, M, D = 3, 96, 8
    scores = jnp.asarray(rng.uniform(size=(B, M)), jnp.float32)
    feats = rng.normal(size=(M, D)).astype(np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True)
    mask = jnp.asarray(rng.uniform(size=(B, M)) > 0.3)
    slates, _ = serve_rerank(
        scores, jnp.asarray(feats),
        DPPRerankConfig(slate_size=6, shortlist=48), mask=mask,
    )
    assert slates.shape == (B, 6)
    for b in range(B):
        for i in np.asarray(slates[b]):
            if i >= 0:
                assert bool(mask[b, i])


# ---------------------------------------------------------------------------
# The per-device tile and precision
# ---------------------------------------------------------------------------


def _compiled_platform(interpret=None):
    return False if interpret is None else bool(interpret)


@pytest.mark.parametrize("D,Mloc,R,windowed", [
    (32, 250_000, 20, False),  # pool1m's shard on four chips
    (32, 250_000, 8, True),
    (64, 262_144, 50, False),
    (16, 500, 12, False),  # a shard that fits on-core
    (256, 1 << 20, 50, True),
    (32, 49_152, 20, False),  # fits on-core, wider than a tile may be
    (100, 1000, 50, False),  # feed1k's shortlist as a shard
    (200_000, 1 << 20, 16, False),  # not one lane-width tile fits
])
def test_mesh_tile_on_a_compiled_platform(monkeypatch, D, Mloc, R, windowed):
    """Unset, the mesh's tile is the one-chip ``plan_tile`` at the
    shard's shape where Pallas runs compiled: its tiled answer as is; a
    resident answer (the mesh has no resident kernel) one tile over the
    shard, capped at the widest tile that fits; a jnp answer the jnp
    step bodies.  A tile is a LANE multiple within the per-tile budget,
    no wider than the shard."""
    from repro.core import sharded
    from repro.kernels.dpp_greedy.tiling import (
        LANE, VMEM_BUDGET_BYTES, auto_tile, plan_tile, round_up,
        tile_vmem_bytes,
    )

    monkeypatch.setattr(sharded, "resolve_interpret", _compiled_platform)
    mode, tm = plan_tile(D, Mloc, R, windowed)
    want = {
        "tiled": tm,
        "resident": min(round_up(Mloc, LANE), auto_tile(D, R, windowed)),
        "jnp": None,
    }[mode]
    tile, source = sharded._mesh_tile(None, D, Mloc, R, windowed)
    assert (tile, source) == (want, "model")
    if tile is not None:
        assert tile % LANE == 0 and LANE <= tile <= round_up(Mloc, LANE)
        assert tile_vmem_bytes(D, tile, R, windowed) <= VMEM_BUDGET_BYTES
    # an explicit tile wins on any platform
    assert sharded._mesh_tile(2048, D, Mloc, R, windowed) == (2048, "explicit")


def test_mesh_tile_off_a_tpu_keeps_the_jnp_bodies():
    from repro.core import sharded

    assert jax.default_backend() != "tpu"
    assert sharded._mesh_tile(None, 32, 250_000, 20, False) == (None, None)


def _dot_precisions(jaxpr):
    """The ``precision`` of every ``dot_general`` in ``jaxpr`` and the
    jaxprs nested in it."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _dot_precisions(inner)
    return out


@pytest.mark.parametrize("window", [None, 3], ids=["exact", "windowed"])
def test_jnp_step_bodies_multiply_V_at_highest_precision(window):
    """Every product of V in the mesh's jnp step bodies asks for
    ``Precision.HIGHEST``, whole slate and streamed chunk alike: a TPU's
    default float32 matmul rounds its operands through bfloat16."""
    from repro.core.sharded import (
        dpp_greedy_sharded_stream_chunk,
        dpp_greedy_sharded_stream_init,
    )

    V = _problem(5)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    hi = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    whole = jax.make_jaxpr(
        lambda V: dpp_greedy_sharded(V, 6, mesh=mesh, window=window))(V)
    state = dpp_greedy_sharded_stream_init(V, 6, mesh=mesh, window=window)
    chunk = jax.make_jaxpr(
        lambda V, st: dpp_greedy_sharded_stream_chunk(V, st, 3, mesh=mesh)
    )(V, state)
    for jaxpr in (whole.jaxpr, chunk.jaxpr):
        precisions = _dot_precisions(jaxpr)
        assert precisions and all(p == hi for p in precisions), precisions


@pytest.mark.slow
@pytest.mark.parametrize("tile_m", [None, 128], ids=["tile-unset", "tile-128"])
@pytest.mark.parametrize("window", [None, 4], ids=["exact", "windowed"])
def test_mesh_rerank_matches_float64_reference_on_four_devices(window, tile_m):
    """``Reranker.rerank`` on a 4-device mesh, whole slate and streamed,
    selects the float64 reference's slate (``bench/reference.py``) index
    for index, with the tile unset (the jnp step bodies, off a TPU) and
    set (the Pallas step kernel, interpreted); the dispatch counters say
    which ran and count k merges of each kind per call."""
    out = run_subprocess(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from bench import reference
        from repro import obs
        from repro.obs import ObsConfig
        from repro.serving import DPPRerankConfig, Reranker, RerankRequest
        assert jax.device_count() == 4
        mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(11)
        M, D, k, alpha = 1001, 16, 10, 3.0  # M pads to the mesh quantum
        feats = rng.normal(size=(M, D)).astype(np.float32)
        feats /= np.linalg.norm(feats, axis=1, keepdims=True)
        scores = rng.uniform(size=M).astype(np.float32)
        V = reference.kernel_columns(feats, scores, alpha)
        ref, _ = reference.ref_greedy(V, k, 1e-6, window={window!r})
        session = obs.enable(ObsConfig(enabled=True, trace=False))
        cfg = DPPRerankConfig(slate_size=k, shortlist=M, alpha=alpha,
                              eps=1e-6, window={window!r}, mesh=mesh,
                              tile_m={tile_m!r}, chunk_size=4)
        rr = Reranker(cfg)
        req = RerankRequest(scores=jnp.asarray(scores),
                            feats=jnp.asarray(feats))
        ids, _ = rr.rerank(req)
        streamed = np.concatenate([np.asarray(c) for c, _ in rr.stream(req)])
        snap = session.registry.snapshot()["counters"]
        print(json.dumps({{
            "ref": [int(i) for i in ref], "ids": np.asarray(ids).tolist(),
            "streamed": streamed.tolist(),
            "modes": snap["dpp_kernel_dispatch_total"],
            "merges": snap["sharded_merges_total"]}}))
    """)
    got = json.loads(out.strip().splitlines()[-1])
    assert len(got["ref"]) == 10
    assert got["ids"] == got["ref"]
    assert got["streamed"] == got["ref"]
    mode = "jnp" if tile_m is None else "tiled"
    windowed = str(window is not None)
    # one whole-slate call and one stream init
    assert got["modes"] == {f"mode={mode},windowed={windowed}": 2}
    kinds = ["argmax", "winner"] + (["window"] if window else [])
    # k merges of each kind for the call, k more over the stream's chunks
    assert got["merges"] == {f"kind={kind}": 20 for kind in kinds}


# ---------------------------------------------------------------------------
# Multi-device property test (subprocess, slow lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_matches_lowrank_multidevice_property():
    """Hypothesis: on an 8-host-device mesh, sharded greedy selects the
    identical slate as the single-device low-rank path (d_hist equal to
    ~1 ulp) — exact and windowed modes, M divisible by P or padded,
    masked or not."""
    pytest.importorskip("hypothesis")
    run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from hypothesis import given, settings, strategies as st
        from repro.core import dpp_greedy_sharded, dpp_greedy_lowrank
        from repro.core.windowed import dpp_greedy_windowed_lowrank
        assert jax.device_count() == 8
        import jax
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))

        @settings(max_examples=20, deadline=None)
        @given(
            seed=st.integers(0, 2**31 - 1),
            M=st.integers(16, 200),
            D=st.integers(4, 32),
            k=st.integers(1, 12),
            window=st.one_of(st.none(), st.integers(1, 6)),
            masked=st.booleans(),
        )
        def check(seed, M, D, k, window, masked):
            # stay in the full-rank regime (k <= D): past the kernel's
            # numerical rank the marginal gains are f32 cancellation
            # noise and argmax order is not meaningful (the paper's
            # eq.-20 eps-stop exists to halt selection there)
            k = min(k, D)
            rng = np.random.default_rng(seed)
            V = jnp.asarray(rng.normal(size=(D, M)), jnp.float32) / np.sqrt(D)
            mask = jnp.asarray(rng.uniform(size=M) > 0.3) if masked else None
            if window is None or window >= k:
                ref = dpp_greedy_lowrank(V, k, eps=1e-6, mask=mask)
            else:
                ref = dpp_greedy_windowed_lowrank(
                    V, k, window=window, eps=1e-6, mask=mask)
            got = dpp_greedy_sharded(
                V, k, mesh=mesh, window=window, eps=1e-6, mask=mask)
            np.testing.assert_array_equal(
                np.asarray(ref.indices), np.asarray(got.indices))
            # XLA may compile the per-shard (D, M/P) reductions with a
            # different op order than the (D, M) single-device shapes, so
            # d_hist is identical only to ~1 ulp, not bitwise
            np.testing.assert_allclose(
                np.asarray(ref.d_hist), np.asarray(got.d_hist),
                rtol=1e-6, atol=1e-7)
            assert int(ref.n_selected) == int(got.n_selected)

        check()
        print("SHARDED-PROPERTY-OK")
    """)


@pytest.mark.slow
def test_sharded_rerank_multidevice_serving_parity():
    """8-device sharded rerank (sharded top-k shortlist + sharded greedy)
    returns the identical slate to the single-device serving path."""
    run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import sharded_topk
        from repro.serving import DPPRerankConfig, Reranker, RerankRequest
        def rr(s, f, cfg, mask=None):
            return Reranker(cfg).rerank(
                RerankRequest(scores=s, feats=f, mask=mask))
        assert jax.device_count() == 8
        import jax
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(0)
        M, D = 3001, 16  # deliberately not divisible by 8 (padded shards)
        scores = jnp.asarray(rng.uniform(size=M), jnp.float32)
        feats = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
        feats = feats / jnp.linalg.norm(feats, axis=1, keepdims=True)
        mask = jnp.asarray(rng.uniform(size=M) > 0.2)
        v1, i1 = jax.lax.top_k(scores, 500)
        v2, i2 = sharded_topk(scores, 500, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        # window=1 is the regression case for the PartitionId SPMD
        # lowering failure (axis_index must stay hoisted out of the loop)
        for window in (None, 1, 5):
            for m in (None, mask):
                dense, _ = rr(scores, feats, DPPRerankConfig(
                    slate_size=16, shortlist=500, alpha=3.0, eps=1e-6,
                    window=window), mask=m)
                sh, _ = rr(scores, feats, DPPRerankConfig(
                    slate_size=16, shortlist=500, alpha=3.0, eps=1e-6,
                    window=window, mesh=mesh), mask=m)
                np.testing.assert_array_equal(np.asarray(dense), np.asarray(sh))
        print("SHARDED-SERVING-OK")
    """)


@pytest.mark.slow
def test_rerank_batch_sharded_multidevice_parity():
    """Acceptance bar for the users x candidates composition: on an
    8-host-device mesh, a batched request with cfg.mesh returns slates
    identical index-for-index (d_hist to ~1 ulp) to vmap of the
    single-device dispatch for B >= 4 users with per-user masks, padded
    M (not divisible by P), and per-user eps-stop."""
    run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.serving import DPPRerankConfig, Reranker, RerankRequest
        def rr(s, f, cfg, mask=None):
            return Reranker(cfg).rerank(
                RerankRequest(scores=s, feats=f, mask=mask))
        assert jax.device_count() == 8
        import jax
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(1)
        B, M, D = 5, 1501, 12  # M not divisible by 8 (padded shards)
        scores = jnp.asarray(rng.uniform(size=(B, M)), jnp.float32)
        feats = rng.normal(size=(M, D)).astype(np.float32)
        feats /= np.linalg.norm(feats, axis=1, keepdims=True)
        feats = jnp.asarray(feats)
        mask = jnp.asarray(rng.uniform(size=(B, M)) > 0.25)
        for window in (None, 1, 4):
            for m in (None, mask):
                kw = dict(slate_size=10, shortlist=400, alpha=3.0,
                          eps=1e-6, window=window)
                ref, ref_dh = rr(
                    scores, feats, DPPRerankConfig(**kw), mask=m)
                got, got_dh = rr(
                    scores, feats, DPPRerankConfig(mesh=mesh, **kw), mask=m)
                np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
                np.testing.assert_allclose(
                    np.asarray(ref_dh), np.asarray(got_dh),
                    rtol=1e-6, atol=1e-7)
        # per-user eps-stop: rank-deficient per-user kernels (D=3) halt
        # at different steps per user; batched sharded must agree
        Bs, Ms, Ds = 4, 400, 3
        s2 = jnp.asarray(rng.uniform(size=(Bs, Ms)), jnp.float32)
        f2 = rng.normal(size=(Bs, Ms, Ds)).astype(np.float32)
        f2 /= np.linalg.norm(f2, axis=-1, keepdims=True)
        f2 = jnp.asarray(f2)
        kw = dict(slate_size=8, shortlist=200, alpha=2.0, eps=1e-2)
        ref, _ = rr(s2, f2, DPPRerankConfig(**kw))
        got, _ = rr(s2, f2, DPPRerankConfig(mesh=mesh, **kw))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        assert (np.asarray(got) == -1).any()
        print("SHARDED-BATCH-OK")
    """)
