"""Continuous-batching router (repro.serving.router) differential suite.

The core guarantee: R concurrent heterogeneous requests — different M,
k, mask, arrival time — coalesced into one slot-batched micro-batch
produce slates **index-for-index equal** to a per-request
``Reranker.rerank`` on the same inputs, whatever order they arrive and
interleave in (a hypothesis property over arrival schedules, plus
seeded deterministic coverage for environments without hypothesis).
Around it: eps-stopped lanes free their slot for queued requests,
deadline eviction returns the partial slate with ``timed_out=True``,
admission is FIFO under a full queue (no starvation), overflow is
refused with ``RouterQueueFull``, and the stats hook sees the gauges
move.

Slow lane: the same differential on an 8-host-device mesh (sharded
backend) in a subprocess, per the dry-run isolation contract.
"""
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest
import jax.numpy as jnp

from repro.serving import (
    DPPRerankConfig,
    Reranker,
    RerankRequest,
    RouterConfig,
)
from repro.serving.router import RouterQueueFull

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def make_request(seed, M, k=None, masked=False, D=8, **kw):
    rng = np.random.default_rng(seed)
    f = rng.normal(size=(M, D)).astype(np.float32)
    f /= np.maximum(np.linalg.norm(f, axis=1, keepdims=True), 1e-12)
    s = rng.uniform(0.1, 1.0, size=M).astype(np.float32)
    mask = None
    if masked:
        m = np.ones(M, bool)
        m[rng.choice(M, size=M // 4, replace=False)] = False
        mask = jnp.asarray(m)
    return RerankRequest(scores=jnp.asarray(s), feats=jnp.asarray(f),
                         slate_size=k, mask=mask, **kw)


def session(slots=2, chunk=3, bucket=32, k=8, window=None, use_kernel=False,
            max_queue=32, **cfg_kw):
    cfg = DPPRerankConfig(slate_size=k, shortlist=bucket, alpha=3.0,
                          window=window, use_kernel=use_kernel,
                          chunk_size=chunk, **cfg_kw)
    return Reranker(cfg, router_config=RouterConfig(
        slots=slots, chunk_size=chunk, max_candidates=bucket,
        max_queue=max_queue,
    ))


def assert_router_matches_rerank(rr, reqs, schedule=None):
    """Submit ``reqs`` interleaved with pumps per ``schedule`` (pumps
    to run after each submit; None = all up front), drain, and compare
    every slate to the per-request path."""
    expect = [tuple(np.asarray(x) for x in rr.rerank(r)) for r in reqs]
    handles = []
    for i, r in enumerate(reqs):
        handles.append(rr.submit(r))
        for _ in range(schedule[i] if schedule else 0):
            rr.router.pump()
    rr.router.drain()
    for h, (ei, ed), r in zip(handles, expect, reqs):
        gi, gd = h.result()
        k = r.slate_size if r.slate_size is not None else rr.cfg.slate_size
        assert len(gi) == k and not h.timed_out
        np.testing.assert_array_equal(gi, ei)
        np.testing.assert_allclose(gd, ed, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# Differential parity, heterogeneous and interleaved
# ---------------------------------------------------------------------------


def test_concurrent_heterogeneous_parity():
    rr = session(slots=3, chunk=3, bucket=32, k=8)
    reqs = [
        make_request(1, 40, k=8),
        make_request(2, 24, k=5),
        make_request(3, 48, k=7, masked=True),
        make_request(4, 16, k=3),
        make_request(5, 32, k=8, masked=True),
    ]
    assert_router_matches_rerank(rr, reqs)
    st = rr.router.stats
    assert st.completed == 5 and st.slot_occupancy == 0
    assert st.fill_ratio > 0


@pytest.mark.parametrize("seed", range(4))
def test_interleaved_arrivals_seeded(seed):
    """Deterministic arrival-order coverage: random pump interleaving
    between submits must not change any slate."""
    rng = np.random.default_rng(seed)
    rr = session(slots=2, chunk=2, bucket=24, k=6)
    reqs = [
        make_request(100 + seed * 10 + i, int(rng.choice([16, 20, 24])),
                     k=int(rng.integers(2, 7)), masked=bool(rng.integers(2)))
        for i in range(5)
    ]
    schedule = [int(rng.integers(0, 4)) for _ in reqs]
    assert_router_matches_rerank(rr, reqs, schedule)


def test_interleaved_arrivals_property():
    hyp = pytest.importorskip("hypothesis")  # noqa: F841
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        ms=st.lists(st.sampled_from([16, 24, 32]), min_size=2, max_size=5),
        pumps=st.lists(st.integers(0, 4), min_size=5, max_size=5),
    )
    def check(seed, ms, pumps):
        rng = np.random.default_rng(seed)
        rr = session(slots=2, chunk=2, bucket=32, k=6)
        reqs = [
            make_request(seed + i, m, k=int(rng.integers(2, 7)),
                         masked=bool(rng.integers(2)))
            for i, m in enumerate(ms)
        ]
        assert_router_matches_rerank(rr, reqs, pumps[: len(reqs)])

    check()


@pytest.mark.parametrize("window", [None, 3])
def test_pallas_router_parity(window):
    rr = session(slots=2, chunk=3, bucket=48, k=6, window=window,
                 use_kernel=True)
    reqs = [make_request(20 + i, 40 + 4 * i, k=6 - (i % 2), masked=(i == 1))
            for i in range(3)]
    assert_router_matches_rerank(rr, reqs, schedule=[0, 2, 1])


def test_windowed_router_parity_jnp():
    rr = session(slots=2, chunk=2, bucket=24, k=6, window=3)
    reqs = [make_request(30 + i, 24, k=6) for i in range(3)]
    assert_router_matches_rerank(rr, reqs)


# ---------------------------------------------------------------------------
# Slot lifecycle: eps-stop reuse, deadlines, backpressure, starvation
# ---------------------------------------------------------------------------


def _rank1_request(seed, M=24, k=8):
    """All-identical features: the DPP eps-stops after one pick."""
    rng = np.random.default_rng(seed)
    f = np.tile(rng.normal(size=(1, 8)), (M, 1)).astype(np.float32)
    f /= np.linalg.norm(f, axis=1, keepdims=True)
    s = rng.uniform(0.5, 1.0, size=M).astype(np.float32)
    return RerankRequest(scores=jnp.asarray(s), feats=jnp.asarray(f),
                         slate_size=k)


def test_eps_stop_frees_slot_for_queued_request():
    rr = session(slots=1, chunk=2, bucket=24, k=8, eps=1e-3)
    stopper = _rank1_request(0)
    follower = make_request(1, 24, k=8)
    exp_stop = np.asarray(rr.rerank(stopper)[0])
    exp_follow = np.asarray(rr.rerank(follower)[0])
    h1, h2 = rr.submit(stopper), rr.submit(follower)
    gi1, _ = h1.result()
    gi2, _ = h2.result()
    np.testing.assert_array_equal(gi1, exp_stop)
    np.testing.assert_array_equal(gi2, exp_follow)
    # the stopper kept the whole-slate contract: length k, -1 fill
    assert len(gi1) == 8 and (gi1 == -1).sum() >= 6
    st = rr.router.stats
    assert st.eps_stopped >= 1 and st.completed == 2
    # the single slot served both: the eps-stop freed it mid-flight
    assert rr.router.rcfg.slots == 1


def test_deadline_eviction_partial_slate():
    rr = session(slots=1, chunk=2, bucket=32, k=10)
    h = rr.submit(make_request(2, 32, k=10, deadline=1e-9))
    rr.router.pump()  # admits + launches the first chunk
    time.sleep(0.005)
    rr.router.drain()
    gi, gd = h.result()
    assert h.timed_out
    assert len(gi) < 10  # partial, not -1-padded to k
    assert len(gi) == len(gd)
    assert rr.router.stats.timed_out == 1


def test_deadline_expires_in_queue():
    rr = session(slots=1, chunk=2, bucket=24, k=6)
    blocker = rr.submit(make_request(3, 24, k=6))
    queued = rr.submit(make_request(4, 24, k=6, deadline=1e-9))
    time.sleep(0.005)
    rr.router.drain()
    assert not blocker.timed_out and len(blocker.result()[0]) == 6
    assert queued.timed_out and len(queued.result()[0]) == 0


def test_backpressure_and_counters():
    rr = session(slots=1, chunk=2, bucket=16, k=4, max_queue=2)
    reqs = [make_request(10 + i, 16, k=4) for i in range(3)]
    hs = [rr.submit(r) for r in reqs[:2]]
    with pytest.raises(RouterQueueFull):
        rr.submit(reqs[2])
    assert rr.router.stats.rejected == 1
    assert rr.router.stats.queue_depth == 2
    rr.router.drain()
    assert all(h.done for h in hs)
    # after draining there is room again
    h3 = rr.submit(reqs[2])
    rr.router.drain()
    assert h3.done and not h3.timed_out


def test_no_starvation_fifo_under_full_queue():
    """Every request admitted under a persistently full queue completes,
    and first-come requests never finish after later arrivals that
    queued behind them on the same slot."""
    rr = session(slots=1, chunk=2, bucket=16, k=4, max_queue=8)
    reqs = [make_request(40 + i, 16, k=4, rid=i) for i in range(8)]
    handles = [rr.submit(r) for r in reqs]
    finish_order = []
    while not all(h.done for h in handles):
        rr.router.pump()
        for h in handles:
            if h.done and h.rid not in finish_order:
                finish_order.append(h.rid)
    assert finish_order == sorted(finish_order)  # FIFO through one slot
    assert rr.router.stats.completed == 8


def test_submit_validation():
    rr = session(slots=1, chunk=2, bucket=16, k=4)
    s, f = np.ones((2, 16), np.float32), np.ones((16, 8), np.float32)
    with pytest.raises(ValueError, match="single requests"):
        rr.submit(RerankRequest(scores=jnp.asarray(s), feats=jnp.asarray(f)))
    with pytest.raises(ValueError, match="slot capacity"):
        rr.submit(make_request(0, 16, k=9))
    with pytest.raises(ValueError, match="bucket"):
        rr.submit(make_request(0, 64, k=4, shortlist=64))
    rr.submit(make_request(0, 16, k=4))
    with pytest.raises(ValueError, match="feature dim"):
        rr.submit(make_request(0, 16, k=4, D=12))
    rr.router.drain()


def test_metrics_hook_sees_gauges():
    seen = []
    cfg = DPPRerankConfig(slate_size=4, shortlist=16, chunk_size=2)
    rr = Reranker(cfg, router_config=RouterConfig(
        slots=2, chunk_size=2, max_candidates=16,
        metrics_hook=lambda snap: seen.append(
            (snap.slot_occupancy, snap.queue_depth, snap.fill_ratio)
        ),
    ))
    hs = [rr.submit(make_request(50 + i, 16)) for i in range(3)]
    rr.router.drain()
    assert all(h.done for h in hs)
    assert any(occ == 2 for occ, _, _ in seen)  # both slots were busy
    assert seen[-1][0] == 0  # and the hook saw the drain
    assert all(h.ttfc is not None and h.ttfc >= 0 for h in hs)
    st = rr.router.stats
    assert st.ttfc_count == len(hs)
    assert st.mean_ttfc == pytest.approx(
        np.mean([h.ttfc for h in hs]), rel=1e-6
    )


def test_router_ttfc_beats_serial_burst():
    """The acceptance ordering on a heterogeneous burst: continuous
    batching must not serve first chunks slower than request-at-a-time
    streaming.  Serial streaming folds each request's k into the
    compiled state geometry (request i also waits for slates 0..i-1);
    the router's fixed slot capacity serves every k from one compiled
    geometry — per-request knobs stay in data (fig7 gates the same
    ordering end-to-end)."""
    rr = session(slots=4, chunk=4, bucket=128, k=16)
    ks = [16, 13, 14, 11, 9, 15, 10, 12]  # heterogeneous slate lengths
    reqs = [make_request(60 + i, 256, k=k, D=16) for i, k in enumerate(ks)]
    # warm both paths on the FIRST request's geometry only — the point
    # under test is how each path serves the shapes it has not seen
    for c, _ in rr.stream(reqs[0]):
        c.block_until_ready()
    rr.submit(reqs[0]).result()
    t0 = time.perf_counter()
    serial = []
    for r in reqs:
        first = None
        for c, _ in rr.stream(r):
            c.block_until_ready()
            if first is None:
                first = time.perf_counter() - t0
        serial.append(first)
    handles = [rr.submit(r) for r in reqs]
    rr.router.drain()
    routed = [h.ttfc for h in handles]
    assert np.mean(routed) <= np.mean(serial)


# ---------------------------------------------------------------------------
# Multi-device router parity (subprocess, slow lane)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_router_multidevice_sharded_parity():
    """The router on an 8-device mesh: heterogeneous k/mask requests on
    sharded slot states match per-request sharded rerank."""
    run_subprocess("""
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
        import numpy as np
        import jax.numpy as jnp
        from repro.serving import (
            DPPRerankConfig, Reranker, RerankRequest, RouterConfig,
        )

        import jax
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        M = 64  # bucket: every request padded to the full sharded width
        cfg = DPPRerankConfig(slate_size=6, shortlist=48, alpha=3.0,
                              mesh=mesh, chunk_size=2)
        rr = Reranker(cfg, router_config=RouterConfig(
            slots=2, chunk_size=2, max_candidates=M))

        def req(seed, m, k, masked):
            rng = np.random.default_rng(seed)
            f = rng.normal(size=(m, 8)).astype(np.float32)
            f /= np.linalg.norm(f, axis=1, keepdims=True)
            s = rng.uniform(0.1, 1.0, size=m).astype(np.float32)
            mask = None
            if masked:
                mm = np.ones(m, bool); mm[::3] = False
                mask = jnp.asarray(mm)
            return RerankRequest(scores=jnp.asarray(s),
                                 feats=jnp.asarray(f), slate_size=k,
                                 mask=mask)

        reqs = [req(0, 64, 6, False), req(1, 48, 4, True),
                req(2, 64, 5, False), req(3, 56, 6, True)]
        expect = [tuple(np.asarray(x) for x in rr.rerank(r)) for r in reqs]
        handles = [rr.submit(r) for r in reqs]
        rr.router.drain()
        for h, (ei, ed), r in zip(handles, expect, reqs):
            gi, gd = h.result()
            assert len(gi) == r.slate_size
            np.testing.assert_array_equal(gi, ei)
            np.testing.assert_allclose(gd, ed, rtol=1e-4, atol=1e-6)
        assert rr.router.stats.completed == 4
        print("ok")
    """)
