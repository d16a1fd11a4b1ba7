"""Streaming slate emission — the differential harness.

Every backend's chunk-emitting executor is locked to the whole-slate
result and, through the shared ``greedy_oracle`` fixture, to the one
jnp rebuild oracle:

* ``greedy_map_chunks`` chunks concatenate index-for-index (d_hist to
  ~1 ulp) to ``greedy_map`` for every backend × window × chunk_size ×
  ragged-M × mask combination;
* a hypothesis property pins the stronger invariant: *any prefix* of
  chunks equals the whole-slate prefix (streaming can be cut off at any
  chunk boundary and what was already emitted is final);
* ``Reranker.stream`` equals ``Reranker.rerank`` through the serving
  layer (shortlist, global-id mapping, per-chunk d_hist), sharded
  included;
* the fused Pallas chunk executor makes exactly **one** pallas_call —
  one HBM C/d2 round-trip — per chunk, not one per step (checked
  structurally on the jaxpr), while the whole-slate tiled driver keeps
  its per-step launch inside the loop;
* ``GreedySpec``/``DPPRerankConfig`` validation: ``chunk_size`` on a
  backend that would silently ignore it fails at construction.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from conftest import (
    assert_greedy_parity,
    make_greedy_inputs,
    serve_rerank,
    serve_rerank_stream,
)
from repro.core import (
    GreedySpec,
    GreedySpecError,
    greedy_chunk,
    greedy_init,
    greedy_map,
    greedy_map_chunks,
    greedy_step,
)
from repro.serving.reranker import DPPRerankConfig

BACKENDS = ["jnp", "pallas_resident", "pallas_tiled", "sharded",
            "sharded_tiled"]


def _spec(backend, k, window, chunk=None, eps=1e-6):
    """GreedySpec for one differential backend.  ``pallas_resident``
    leaves tile_m to the shapes (resident-size problems stream as one
    whole-M tile); ``pallas_tiled`` forces multi-tile sweeps."""
    tile = 128
    if backend == "jnp":
        # the jnp spec cannot carry chunk_size (GreedySpec rejects it);
        # the streaming calls pass it explicitly
        return GreedySpec(k=k, window=window, backend="jnp", eps=eps)
    if backend == "pallas_resident":
        return GreedySpec(k=k, window=window, backend="pallas", eps=eps,
                          chunk_size=chunk)
    if backend == "pallas_tiled":
        return GreedySpec(k=k, window=window, backend="pallas", eps=eps,
                          tile_m=tile, chunk_size=chunk)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    tm = tile if backend == "sharded_tiled" else None
    return GreedySpec(k=k, window=window, backend="sharded", mesh=mesh,
                      eps=eps, tile_m=tm, chunk_size=chunk)


def _collect(spec, V, mask, chunk):
    sels, dhs = [], []
    for res in greedy_map_chunks(spec, V=V, mask=mask, chunk_size=chunk):
        sels.append(np.asarray(res.indices))
        dhs.append(np.asarray(res.d_hist))
    return sels, dhs


# ---------------------------------------------------------------------------
# The core differential: chunks concatenate to the whole slate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("window", [None, 3, 1])
@pytest.mark.parametrize("chunk", [1, 4, 16])
def test_chunks_concatenate_to_whole(backend, window, chunk):
    """backend × window × chunk_size × ragged M × mask: the streamed
    chunks concatenate index-for-index (d_hist ~1 ulp) to greedy_map."""
    D, M, k = 16, 137, 10  # M ragged: every kernel/sharded path pads
    V = make_greedy_inputs(11 + (window or 0), None, D, M)
    rng = np.random.default_rng(5)
    mask = jnp.asarray(rng.uniform(size=M) > 0.3)
    whole = greedy_map(_spec(backend, k, window), V=V, mask=mask)
    sels, dhs = _collect(_spec(backend, k, window, chunk), V, mask, chunk)
    sizes = [s.shape[-1] for s in sels]
    assert sum(sizes) == k and max(sizes) <= chunk  # ragged tail covered
    np.testing.assert_array_equal(
        np.concatenate(sels), np.asarray(whole.indices)
    )
    np.testing.assert_allclose(
        np.concatenate(dhs), np.asarray(whole.d_hist), rtol=1e-6, atol=1e-7
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_streamed_slate_matches_oracle(greedy_oracle, backend):
    """The concatenated stream is pinned to the shared oracle itself,
    not merely to this backend's whole-slate path."""
    D, M, k, w, chunk = 16, 90, 8, 3, 3
    V = make_greedy_inputs(23, None, D, M)
    rng = np.random.default_rng(6)
    mask = jnp.asarray(rng.uniform(size=M) > 0.25)
    sels, dhs = _collect(_spec(backend, k, w, chunk), V, mask, chunk)
    assert_greedy_parity(
        greedy_oracle, np.concatenate(sels), np.concatenate(dhs),
        V, k, window=w, mask=mask,
    )


@pytest.mark.parametrize("backend", ["jnp", "pallas_tiled", "sharded"])
def test_eps_stop_latches_across_chunks(backend):
    """A rank-deficient kernel stops mid-stream: the stop must latch at
    the same step as the whole-slate path and every later chunk must
    hold -1 / 0."""
    D, M, k, chunk = 5, 160, 12, 4
    V = make_greedy_inputs(31, None, D, M)
    whole = greedy_map(_spec(backend, k, None, eps=1e-3), V=V)
    sels, dhs = _collect(
        _spec(backend, k, None, chunk, eps=1e-3), V, None, chunk
    )
    sel = np.concatenate(sels)
    np.testing.assert_array_equal(sel, np.asarray(whole.indices))
    assert (sel == -1).any(), "eps-stop never fired — the case is vacuous"
    np.testing.assert_allclose(
        np.concatenate(dhs), np.asarray(whole.d_hist), rtol=1e-6, atol=1e-7
    )


def test_greedy_step_and_mixed_chunks():
    """The raw init/step/chunk API: single steps interleaved with chunks
    resume exactly where the state left off."""
    D, M, k = 12, 100, 9
    V = make_greedy_inputs(41, None, D, M)
    spec = GreedySpec(k=k, window=4, backend="jnp", eps=1e-6)
    whole = greedy_map(spec, V=V)
    state = greedy_init(spec, V=V)
    out = []
    state, i0, d0 = greedy_step(spec, state, V=V)
    out.append([int(i0)])
    state, sel, _ = greedy_chunk(spec, state, V=V, chunk_size=5)
    out.append(np.asarray(sel))
    state, sel, _ = greedy_chunk(spec, state, V=V, chunk_size=3)
    out.append(np.asarray(sel))
    np.testing.assert_array_equal(
        np.concatenate(out), np.asarray(whole.indices)
    )


def test_batched_pallas_chunks():
    """The fused chunk kernels carry a user batch; per-user eps-stop
    latches independently."""
    B, D, M, k, chunk = 3, 10, 140, 8, 3
    V = make_greedy_inputs(47, B, D, M)
    mask = jnp.asarray(np.random.default_rng(8).uniform(size=(B, M)) > 0.3)
    spec = GreedySpec(k=k, window=3, backend="pallas", eps=1e-6,
                      tile_m=128, chunk_size=chunk)
    whole = greedy_map(
        GreedySpec(k=k, window=3, backend="pallas", eps=1e-6, tile_m=128),
        V=V, mask=mask,
    )
    chunks = list(greedy_map_chunks(spec, V=V, mask=mask))
    sel = np.concatenate([np.asarray(c.indices) for c in chunks], axis=1)
    assert sel.shape == (B, k)
    np.testing.assert_array_equal(sel, np.asarray(whole.indices))


# ---------------------------------------------------------------------------
# Hypothesis: any prefix of chunks equals the whole-slate prefix
# ---------------------------------------------------------------------------


def test_prefix_of_chunks_equals_whole_prefix_property():
    """Streaming can be cut at any chunk boundary: what was emitted is
    final — every prefix of the chunk sequence equals the whole-slate
    prefix of the same length (jnp backend; the other backends are
    pinned to jnp above)."""
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        M=st.integers(16, 120),
        D=st.integers(4, 24),
        k=st.integers(1, 12),
        window=st.one_of(st.none(), st.integers(1, 6)),
        chunk=st.integers(1, 8),
        masked=st.booleans(),
    )
    def check(seed, M, D, k, window, chunk, masked):
        k = min(k, D)  # full-rank regime (argmax above the noise floor)
        V = make_greedy_inputs(seed, None, D, M, alpha=None)
        rng = np.random.default_rng(seed)
        mask = jnp.asarray(rng.uniform(size=M) > 0.3) if masked else None
        spec = GreedySpec(k=k, window=window, backend="jnp", eps=1e-6)
        whole = np.asarray(greedy_map(spec, V=V, mask=mask).indices)
        sels, _ = _collect(spec, V, mask, chunk)
        done = 0
        for i, s in enumerate(sels):
            done += s.shape[-1]
            prefix = np.concatenate(sels[: i + 1])
            np.testing.assert_array_equal(prefix, whole[:done])
        assert done == k

    check()


# ---------------------------------------------------------------------------
# Serving layer: Reranker.stream == Reranker.rerank
# ---------------------------------------------------------------------------


def _serving_cfgs():
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    tile = 128
    return {
        "jnp": {},
        "pallas": dict(use_kernel=True, tile_m=tile),
        "sharded": dict(mesh=mesh),
        "sharded_tiled": dict(mesh=mesh, tile_m=tile),
    }


@pytest.mark.parametrize("backend", ["jnp", "pallas", "sharded",
                                     "sharded_tiled"])
@pytest.mark.parametrize("window", [None, 4])
def test_rerank_stream_matches_rerank(backend, window):
    """Serving-level differential: global ids and per-chunk d_hist of
    the stream concatenate to the whole-slate rerank — shortlist,
    masking and the ragged final chunk (N % chunk != 0) included."""
    rng = np.random.default_rng(17)
    M, D, N, chunk = 300, 16, 10, 4
    scores = jnp.asarray(rng.uniform(size=M), jnp.float32)
    feats = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    feats = feats / jnp.linalg.norm(feats, axis=1, keepdims=True)
    mask = jnp.asarray(rng.uniform(size=M) > 0.25)
    cfg = DPPRerankConfig(
        slate_size=N, shortlist=128, alpha=3.0, eps=1e-6, window=window,
        chunk_size=chunk, **_serving_cfgs()[backend],
    )
    ref, ref_dh = serve_rerank(scores, feats, cfg, mask=mask)
    chunks = list(serve_rerank_stream(scores, feats, cfg, mask=mask))
    assert [c[0].shape[0] for c in chunks] == [4, 4, 2]
    sel = np.concatenate([np.asarray(c[0]) for c in chunks])
    dh = np.concatenate([np.asarray(c[1]) for c in chunks])
    np.testing.assert_array_equal(sel, np.asarray(ref))
    np.testing.assert_allclose(dh, np.asarray(ref_dh), rtol=1e-6, atol=1e-7)
    # masked items can never be streamed out
    assert all(bool(mask[i]) for i in sel if i >= 0)


def test_rerank_stream_chunk_size_required_and_overridable():
    rng = np.random.default_rng(19)
    M, D = 64, 8
    scores = jnp.asarray(rng.uniform(size=M), jnp.float32)
    feats = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    cfg = DPPRerankConfig(slate_size=6, shortlist=32)
    with pytest.raises(ValueError, match="chunk size"):
        next(serve_rerank_stream(scores, feats, cfg))
    ref, _ = serve_rerank(scores, feats, cfg)
    chunks = list(serve_rerank_stream(scores, feats, cfg, chunk_size=2))
    assert len(chunks) == 3
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(c[0]) for c in chunks]), np.asarray(ref)
    )


# ---------------------------------------------------------------------------
# The fused sweep: one pallas_call — one C/d2 HBM round-trip — per chunk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 3])
def test_fused_chunk_is_one_pallas_call(window):
    """Advancing a chunk of c steps on the pallas backend is ONE fused
    pallas_call (one C/d2 round-trip through HBM), not c per-step
    launches — while the whole-slate tiled driver demonstrably keeps
    its launch inside the step loop."""
    from repro.kernels.dpp_greedy.tiled import pallas_call_structure

    D, M, k, chunk = 12, 256, 8, 4
    V = make_greedy_inputs(53, None, D, M)
    spec = GreedySpec(k=k, window=window, backend="pallas", eps=1e-6,
                      tile_m=128, chunk_size=chunk)
    state = greedy_init(spec, V=V)
    jaxpr = jax.make_jaxpr(
        lambda s, v: greedy_chunk(spec, s, V=v, chunk_size=chunk)
    )(state, V)
    counts = pallas_call_structure(jaxpr)
    assert counts == {"flat": 1, "looped": 0}, counts

    # contrast: the per-step whole-slate tiled driver launches per step
    from repro.kernels.dpp_greedy import dpp_greedy

    jaxpr_whole = jax.make_jaxpr(
        lambda v: dpp_greedy(v, k, window=window, tile_m=128)
    )(V[None])
    whole_counts = pallas_call_structure(jaxpr_whole)
    assert whole_counts["looped"] >= 1, whole_counts


# ---------------------------------------------------------------------------
# Construction-time validation (satellite: mirror the tile_m rule)
# ---------------------------------------------------------------------------


def test_spec_rejects_chunk_size_on_backends_that_ignore_it():
    """chunk_size on the pure-jnp whole-slate path would be silently
    ignored — rejected when the spec is built, exactly as tile_m is."""
    with pytest.raises(GreedySpecError, match="chunk_size"):
        GreedySpec(k=8, backend="jnp", chunk_size=4)
    # auto without a mesh resolves to jnp — also rejected
    with pytest.raises(GreedySpecError, match="chunk_size"):
        GreedySpec(k=8, chunk_size=4)
    with pytest.raises(GreedySpecError, match="chunk_size"):
        GreedySpec(k=8, backend="pallas", chunk_size=0)
    with pytest.raises(GreedySpecError, match="chunk_size"):
        GreedySpec(k=8, backend="pallas", chunk_size=-2)
    # backends with a chunked execution path accept it
    GreedySpec(k=8, backend="pallas", chunk_size=4)
    GreedySpec(k=8, backend="sharded", chunk_size=4,
               mesh=jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,)))
    # serving config mirrors the positivity check, and its greedy_spec()
    # never forwards chunk_size onto a jnp spec
    with pytest.raises(ValueError, match="chunk_size"):
        DPPRerankConfig(chunk_size=0)
    assert DPPRerankConfig(chunk_size=4).greedy_spec().chunk_size is None
    assert (
        DPPRerankConfig(chunk_size=4, use_kernel=True).greedy_spec()
        .chunk_size == 4
    )


def test_streaming_rejects_missing_or_bad_chunk():
    D, M = 8, 64
    V = make_greedy_inputs(59, None, D, M)
    spec = GreedySpec(k=4, backend="jnp")
    with pytest.raises(ValueError, match="chunk size"):
        next(greedy_map_chunks(spec, V=V))
    with pytest.raises(ValueError, match="chunk_size"):
        next(greedy_map_chunks(spec, V=V, chunk_size=0))
    with pytest.raises(ValueError, match="exactly one"):
        greedy_init(spec)
