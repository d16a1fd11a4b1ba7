"""The unified serving API (repro.serving.api): Reranker / RerankRequest
dispatch, construction-time request validation, the streaming prep
hoist, and the legacy-shim *removal* pin.

The PR-6 function-per-shape shims (rerank / rerank_batch /
rerank_stream / sharded_rerank / sharded_rerank_stream) served their
one-release DeprecationWarning grace period and are gone;
``test_legacy_shims_are_removed`` pins that they never come back.
Dispatch correctness is asserted against the module-level
implementation bodies (``_rerank_impl`` & co.) and against per-request
self-consistency — the same ground the shim-comparison tests used to
stand on, minus the shims.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.serving import DPPRerankConfig, Reranker, RerankRequest
from repro.serving.api import _rerank_impl


def _problem(M, D=8, seed=0, batch=None):
    rng = np.random.default_rng(seed)
    shape = (M, D) if batch is None else (batch, M, D)
    f = rng.normal(size=shape).astype(np.float32)
    f /= np.maximum(np.linalg.norm(f, axis=-1, keepdims=True), 1e-12)
    s = rng.uniform(0.1, 1.0, size=shape[:-1]).astype(np.float32)
    return jnp.asarray(s), jnp.asarray(f)


CFG = DPPRerankConfig(slate_size=8, shortlist=32, alpha=3.0, chunk_size=3)


# ---------------------------------------------------------------------------
# RerankRequest: construction-time validation
# ---------------------------------------------------------------------------


def test_request_validates_at_construction():
    s, f = _problem(40)
    for bad in (
        dict(slate_size=0), dict(slate_size=-2), dict(shortlist=0),
        dict(deadline=0.0), dict(deadline=-1.0),
    ):
        with pytest.raises(ValueError):
            RerankRequest(scores=s, feats=f, **bad)
    with pytest.raises(ValueError, match="scores"):
        RerankRequest(scores=s[None, None], feats=f)
    with pytest.raises(ValueError, match="feats"):
        RerankRequest(scores=s, feats=f[None])  # (1, M, D) needs (B, M)
    with pytest.raises(ValueError, match="mask"):
        RerankRequest(scores=s, feats=f, mask=jnp.ones((2, 40), bool))
    req = RerankRequest(scores=s, feats=f, slate_size=5, rid="x")
    assert not req.batched and req.num_candidates == 40


def test_request_batched_shapes():
    s, f = _problem(30, batch=3)
    assert RerankRequest(scores=s, feats=f).batched
    # shared feats with a batch is fine
    RerankRequest(scores=s, feats=f[0])
    RerankRequest(scores=s, feats=f, mask=jnp.ones((3, 30), bool))
    RerankRequest(scores=s, feats=f[0], mask=jnp.ones((30,), bool))


def test_reranker_rejects_non_config():
    with pytest.raises(TypeError, match="DPPRerankConfig"):
        Reranker({"slate_size": 4})
    with pytest.raises(TypeError, match="RerankRequest"):
        Reranker(CFG).rerank(np.zeros(4))


# ---------------------------------------------------------------------------
# Dispatch parity: the session verbs agree with the implementation
# bodies and with each other
# ---------------------------------------------------------------------------


def test_rerank_single_matches_impl():
    s, f = _problem(60, seed=1)
    m = jnp.asarray(np.arange(60) % 4 != 0)
    rr = Reranker(CFG)
    for mask in (None, m):
        new = rr.rerank(RerankRequest(scores=s, feats=f, mask=mask))
        ref = _rerank_impl(s, f, CFG, mask)
        np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(ref[1]))


def test_rerank_batched_dispatch_matches_per_user():
    s, f = _problem(50, seed=2, batch=3)
    rr = Reranker(CFG)
    new = rr.rerank(RerankRequest(scores=s, feats=f))
    assert np.asarray(new[0]).shape == (3, CFG.slate_size)
    for b in range(3):
        one = rr.rerank(RerankRequest(scores=s[b], feats=f[b]))
        np.testing.assert_array_equal(
            np.asarray(new[0][b]), np.asarray(one[0])
        )


def test_request_side_overrides():
    """Per-request k / shortlist fold into the session config without
    touching the session's own defaults."""
    s, f = _problem(60, seed=3)
    rr = Reranker(CFG)
    out, _ = rr.rerank(RerankRequest(scores=s, feats=f, slate_size=4))
    assert np.asarray(out).shape == (4,)
    exp, _ = rr.rerank(
        RerankRequest(scores=s, feats=f, slate_size=4, shortlist=16)
    )
    old, _ = Reranker(
        dataclasses.replace(CFG, slate_size=4, shortlist=16)
    ).rerank(RerankRequest(scores=s, feats=f))
    np.testing.assert_array_equal(np.asarray(exp), np.asarray(old))
    assert rr.cfg.slate_size == 8 and rr.cfg.shortlist == 32


def test_stream_concatenates_to_rerank():
    s, f = _problem(60, seed=4)
    rr = Reranker(CFG)
    req = RerankRequest(scores=s, feats=f)
    whole = np.asarray(rr.rerank(req)[0])
    chunks = [np.asarray(i) for i, _ in rr.stream(req)]
    assert all(len(c) <= CFG.chunk_size for c in chunks)
    np.testing.assert_array_equal(np.concatenate(chunks), whole)


def test_stream_rejects_batched_eagerly():
    s, f = _problem(30, seed=5, batch=2)
    # a plain generator would only raise at the first next(); the session
    # API raises at the call
    with pytest.raises(ValueError, match="single request"):
        Reranker(CFG).stream(RerankRequest(scores=s, feats=f))


def test_stream_prep_is_hoisted(monkeypatch):
    """The O(M) prep — validation, shortlist, state build — runs once at
    the stream() call; generator resumes never re-shortlist."""
    import repro.serving.api as api

    calls = {"n": 0}
    real = api._shortlist_kernel

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(api, "_shortlist_kernel", counting)
    s, f = _problem(60, seed=6)
    gen = Reranker(CFG).stream(RerankRequest(scores=s, feats=f))
    assert calls["n"] == 1  # prep happened at the call, before any next()
    n_chunks = sum(1 for _ in gen)
    assert n_chunks == -(-CFG.slate_size // CFG.chunk_size)
    assert calls["n"] == 1  # and never again on resume


def test_sharded_dispatch_one_device():
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    cfg = DPPRerankConfig(slate_size=6, shortlist=24, alpha=3.0, mesh=mesh,
                          chunk_size=3)
    s, f = _problem(48, seed=7)
    rr = Reranker(cfg)
    new = rr.rerank(RerankRequest(scores=s, feats=f))
    # on a 1-device mesh the sharded path must select the same global
    # ids as the dense single-device dispatch (continuous scores — the
    # documented tie-break divergence is measure-zero)
    dense = Reranker(dataclasses.replace(cfg, mesh=None)).rerank(
        RerankRequest(scores=s, feats=f)
    )
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(dense[0]))
    streamed = np.concatenate(
        [np.asarray(i) for i, _ in rr.stream(RerankRequest(scores=s, feats=f))]
    )
    np.testing.assert_array_equal(streamed, np.asarray(new[0]))


# ---------------------------------------------------------------------------
# The compiled front door: one cached program per phase
# ---------------------------------------------------------------------------

KERNEL_CFG = DPPRerankConfig(slate_size=5, shortlist=32, alpha=3.0,
                             use_kernel=True)


def _fresh_programs():
    """Forget every compiled front-door program, so traces count from 0."""
    import repro.serving.api as api

    api._shortlist_program.clear_cache()
    api._greedy_program.clear_cache()


def _counters(snap, name):
    return snap["counters"].get(name, {})


@pytest.mark.parametrize("kind", [
    "single", "single-masked", "batched", "batched-masked",
    "batched-shared-mask", "batched-per-user-feats", "single-chunked",
])
def test_front_door_traces_each_phase_once_per_kind(kind):
    """N calls of one kind trace each phase's program once; the per-call
    counters count all N, and the slates are the eager body's, index
    for index, with gains to float32 rounding (XLA may fuse the
    relevance map's elementwise ops in the compiled program)."""
    from repro import obs

    N, B, M = 3, 3, 56
    batched = kind.startswith("batched")
    s, f = _problem(M, seed=21, batch=B if batched else None)
    if kind != "batched-per-user-feats" and f.ndim == 3:
        f = f[0]
    rng = np.random.default_rng(21)
    mask = None
    if kind.endswith("masked"):
        mask = jnp.asarray(rng.uniform(size=s.shape) > 0.25)
    elif kind == "batched-shared-mask":
        mask = jnp.asarray(rng.uniform(size=M) > 0.25)
    chunked = kind.endswith("chunked")
    cfg = dataclasses.replace(KERNEL_CFG, chunk_size=2 if chunked else None)
    with jax.disable_jit():
        ref = _rerank_impl(s, f, cfg, mask)
    _fresh_programs()
    rr = Reranker(cfg)
    req = RerankRequest(scores=s, feats=f, mask=mask)
    obs.disable()
    with obs.session(obs.ObsConfig(enabled=True)) as sess:
        for _ in range(N):
            ids, dh = rr.rerank(req)
            np.testing.assert_array_equal(np.asarray(ids),
                                          np.asarray(ref[0]))
            np.testing.assert_allclose(np.asarray(dh), np.asarray(ref[1]),
                                       rtol=1e-6)
        snap = sess.registry.snapshot()
    path = "batched" if batched else "single"
    assert _counters(snap, "serving_rerank_traces_total") == {
        f"path={path},phase=shortlist": 1, f"path={path},phase=greedy": 1}
    assert _counters(snap, "serving_rerank_calls_total") == {
        f"path={path}": N}
    assert _counters(snap, "serving_shortlist_total") == {"path=top_k": N}
    assert _counters(snap, "greedy_dispatch_total") == {
        f"backend=pallas,chunked={chunked}": N}
    mode = "fused_chunk" if chunked else "resident"
    assert _counters(snap, "dpp_kernel_dispatch_total") == {
        f"mode={mode},windowed=False": N}
    assert _counters(snap, "greedy_steps_total") == {
        "backend=pallas": N * (B if batched else 1) * cfg.slate_size}
    if chunked:
        assert _counters(snap, "greedy_chunks_total") == {
            "backend=pallas": N * -(-cfg.slate_size // cfg.chunk_size)}


# ---------------------------------------------------------------------------
# The removal pin (ISSUE 8: the PR-6 shims' grace period has elapsed)
# ---------------------------------------------------------------------------


def test_legacy_shims_are_removed():
    """The five PR-6 deprecation shims are gone from every module that
    carried them — and stay gone.  Anything still importing one belongs
    on the session API (``repro.analysis``'s dead-shim rule flags such
    stragglers statically)."""
    import inspect

    import repro.serving as serving
    import repro.serving.reranker as reranker
    import repro.serving.sharded_rerank as sharded

    for mod, names in (
        (serving, ("rerank", "rerank_batch", "rerank_stream",
                   "sharded_rerank", "sharded_rerank_stream")),
        (reranker, ("rerank", "rerank_batch", "rerank_stream",
                    "_deprecated")),
        (sharded, ("sharded_rerank", "sharded_rerank_stream")),
    ):
        for name in names:
            # importing repro.serving.sharded_rerank binds the
            # *submodule* on the package under the same name the old
            # function used — a module attribute is fine, a callable
            # shim is the resurrection this test pins against
            leftover = getattr(mod, name, None)
            assert leftover is None or inspect.ismodule(leftover), (
                f"{mod.__name__}.{name} was removed in PR 8 after its "
                f"one-release deprecation window; use Reranker/"
                f"RerankRequest instead of resurrecting it"
            )
    for name in ("rerank", "rerank_batch", "rerank_stream",
                 "sharded_rerank", "sharded_rerank_stream"):
        assert name not in serving.__all__
    # the internal builders the session API dispatches through remain
    assert hasattr(reranker, "_shortlist_kernel")
    assert hasattr(sharded, "_sharded_kernel")


# ---------------------------------------------------------------------------
# Whole-pool shortlist: V in id order, no sort, no row gather
# ---------------------------------------------------------------------------

WHOLE_M, WHOLE_B = 40, 3


def _sorted_reference(s, f, cfg, mask):
    """``greedy_map`` over the pool sorted by score and gathered (the
    shortlist as a top-k builds it), ids mapped back through the sort."""
    from repro.core import greedy_map, map_relevance

    s, f = np.asarray(s), np.asarray(f)
    key = s if mask is None else np.where(mask, s, -np.inf)
    order = np.argsort(-key, kind="stable")
    rel = np.asarray(map_relevance(jnp.asarray(s[order]), cfg.alpha))
    m = None if mask is None else np.asarray(mask)[order]
    if m is not None:
        rel = np.where(m, rel, 0.0).astype(np.float32)
    V = jnp.asarray((f[order] * rel[:, None]).T)
    res = greedy_map(cfg.greedy_spec(), V=V,
                     mask=None if m is None else jnp.asarray(m))
    sel = np.asarray(res.indices)
    ids = np.where(sel >= 0, order[np.clip(sel, 0, None)], -1)
    return ids, np.asarray(res.d_hist)


def _whole_pool_case(case):
    """``(served, reference)``, each ``(ids, d_hist)``, for one way of
    reaching the shortlist with ``shortlist >= M``."""
    from repro.serving import RouterConfig

    rng = np.random.default_rng(14)
    batched = case.startswith("batched") or case == "masked-batched"
    s, f = _problem(WHOLE_M, seed=14, batch=WHOLE_B if batched else None)
    if case == "batched-shared":
        f = f[0]
    mask = None
    if case.startswith("masked"):
        mask = jnp.asarray(rng.uniform(size=s.shape) > 0.25)
    cfg = DPPRerankConfig(slate_size=6, shortlist=64, alpha=3.0,
                          chunk_size=4, use_kernel=case == "single-kernel",
                          window=3 if case == "session" else None)
    rr = Reranker(cfg, router_config=RouterConfig(
        slots=2, chunk_size=4, max_candidates=64))
    req = RerankRequest(scores=s, feats=f, mask=mask)
    if case == "stream":
        parts = list(rr.stream(req))
        served = tuple(np.concatenate([np.asarray(p[i]) for p in parts])
                       for i in (0, 1))
    elif case == "router":
        served = rr.submit(req).result()
    elif case == "session":
        served = rr.session(req).next_chunk(4)
    else:
        served = tuple(np.asarray(x) for x in rr.rerank(req))
    if not batched:
        ref = _sorted_reference(s, f, cfg, mask)
    else:
        users = [_sorted_reference(
            s[b], f if f.ndim == 2 else f[b], cfg,
            None if mask is None else mask[b]) for b in range(WHOLE_B)]
        ref = tuple(np.stack([u[i] for u in users]) for i in (0, 1))
    if case == "session":
        ref = tuple(r[:4] for r in ref)
    return served, ref


@pytest.mark.parametrize("case", [
    "single", "single-kernel", "batched-shared", "batched-per-user",
    "masked", "masked-batched", "stream", "router", "session",
])
def test_whole_pool_shortlist_matches_sorted_gather(case):
    """With ``shortlist >= M`` V is built in id order, not sorted and
    gathered; on continuous scores (no exact ties) every path serves the
    slate of the sorted, gathered V, ids mapped back through the sort."""
    (ids, dh), (ref_ids, ref_dh) = _whole_pool_case(case)
    np.testing.assert_array_equal(np.asarray(ids), ref_ids)
    np.testing.assert_allclose(np.asarray(dh), ref_dh, rtol=1e-5)


def _primitives(jaxpr):
    """Every primitive name in ``jaxpr``, nested jaxprs included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                names |= _primitives(sub)
    return names


@pytest.mark.parametrize("masked", [False, True])
def test_whole_pool_shortlist_has_no_sort_or_gather(masked):
    """C == M lowers with no ``top_k`` and no ``gather``; C < M (the
    feed path) still sorts and gathers, unchanged."""
    from repro.serving.reranker import _shortlist_kernel

    s, f = _problem(WHOLE_M, seed=15)
    mask = jnp.asarray(np.arange(WHOLE_M) % 3 != 0) if masked else None
    for shortlist, whole in ((WHOLE_M, True), (64, True), (16, False)):
        cfg = DPPRerankConfig(slate_size=4, shortlist=shortlist, alpha=3.0)
        jaxpr = jax.make_jaxpr(
            lambda s, f, m: _shortlist_kernel(s, f, cfg, m)
        )(s, f, mask).jaxpr
        prims = _primitives(jaxpr)
        assert ("top_k" in prims) is not whole, (shortlist, prims)
        assert ("gather" in prims) is not whole, (shortlist, prims)
