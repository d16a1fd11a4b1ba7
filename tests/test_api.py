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
