"""Observability (repro.obs) suite.

Three contracts:

* **Off means off** — with no session installed every hook is a single
  global read: ``obs.span`` hands back one shared no-op singleton (no
  allocation), the metric hooks return immediately, and the per-call
  cost stays in the nanosecond range.
* **The instruments are correct** — counters/gauges/histograms
  aggregate by sorted label set, the registry refuses kind conflicts,
  the span ring drops oldest-first under pressure, and the Chrome
  ``trace_event`` export round-trips through JSON schema-valid.
* **Telemetry never changes results** — the router produces
  index-for-index identical slates with a session installed, a raising
  ``metrics_hook`` is logged and counted but never kills the pump, and
  the recompile ledger observes what the serving layer claims: zero jit
  cache misses through the warmed router vs at least one per distinct k
  down the per-k serial path.

The CI obs lane re-runs the streaming/router suites with ``REPRO_OBS=1``
(a conftest autouse fixture keeps a session installed throughout) so
every existing differential test doubles as an enabled-path parity test.
"""
import json
import time

import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs
from repro.obs import (
    MetricsRegistry,
    NULL_SPAN,
    ObsConfig,
    SpanTracer,
    validate_chrome_trace,
)
from repro.obs.dispatch import (
    record_chunk,
    record_kernel_dispatch,
    record_sharded_merges,
    record_shortlist,
)

from tests.test_router import make_request, session


@pytest.fixture
def fresh_obs():
    """A session this test owns outright (torn down after), replacing
    whatever the environment (REPRO_OBS lane) installed."""
    obs.disable()
    s = obs.enable(ObsConfig(enabled=True))
    yield s
    obs.disable()


@pytest.fixture
def no_obs():
    """Guaranteed-disabled hooks for the cheap-when-off tests."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# Off by default, near-zero when off
# ---------------------------------------------------------------------------


def test_disabled_span_is_one_shared_singleton(no_obs):
    assert not obs.enabled()
    s = obs.span("anything", M=128, k=8)
    assert s is obs.span("something else") is NULL_SPAN
    with s as inner:  # usable as a context manager, records nothing
        assert inner.set(extra=1) is s
    assert obs.tracer() is None and obs.registry() is None
    # metric hooks are plain returns
    obs.inc("c", 2, backend="jnp")
    obs.gauge_set("g", 1.0)
    obs.observe("h", 0.5)


def test_disabled_hooks_are_nanosecond_cheap(no_obs):
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("hot"):
            pass
        obs.inc("c")
    per_call = (time.perf_counter() - t0) / (2 * n)
    # a generous ceiling (CI boxes jitter); the real disabled cost is a
    # global read + singleton return, ~100ns
    assert per_call < 20e-6, f"disabled hook cost {per_call * 1e6:.2f}us"


def test_disabled_config_is_a_noop_and_session_scopes(no_obs):
    assert obs.enable(ObsConfig(enabled=False)) is None
    assert not obs.enabled()
    with obs.session(ObsConfig(enabled=True)) as s:
        assert obs.enabled() and s is obs.active()
        s2 = obs.enable(ObsConfig(enabled=True))  # kept, not replaced
        assert s2 is s
    assert not obs.enabled()


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_units():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc(backend="jnp")
    c.inc(2, backend="pallas")
    c.inc(backend="jnp")
    assert c.value(backend="jnp") == 2
    assert c.value(backend="pallas") == 2
    assert c.total() == 4
    assert reg.counter("req_total") is c  # get-or-create

    g = reg.gauge("depth")
    g.set(3)
    g.inc(2)
    assert g.value() == 5

    h = reg.histogram("lat_s")
    for v in (0.001, 0.01, 0.1):
        h.observe(v)
    assert h.count() == 3
    assert h.sum() == pytest.approx(0.111)
    assert h.mean() == pytest.approx(0.037)


def test_registry_rejects_kind_conflicts():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="x"):
        reg.gauge("x")


def test_snapshot_and_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(3, backend="jnp", chunked="1")
    reg.gauge("depth", "queue depth").set(2.0)
    reg.histogram("lat_s", "latency").observe(0.02)
    snap = reg.snapshot()
    assert snap["counters"]["req_total"] == {"backend=jnp,chunked=1": 3}
    assert snap["gauges"]["depth"] == {"": 2.0}
    cell = snap["histograms"]["lat_s"][""]
    assert cell["count"] == 1 and cell["sum"] == pytest.approx(0.02)
    # snapshot is JSON-serializable as-is (BENCH_<fig>.json embeds it)
    json.loads(json.dumps(snap))

    text = reg.expose()
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert 'req_total{backend="jnp",chunked="1"} 3' in text
    assert "# TYPE lat_s histogram" in text
    assert 'lat_s_bucket{le="+Inf"} 1' in text


# ---------------------------------------------------------------------------
# Span tracer + Chrome export
# ---------------------------------------------------------------------------


def test_span_ring_drops_oldest_and_counts():
    tr = SpanTracer(ring_size=2)
    for i in range(5):
        with tr.span(f"s{i}", i=i):
            pass
    assert tr.total == 5 and tr.dropped == 3 and len(tr) == 2
    names = [s["name"] for s in tr.finished()]
    assert names == ["s3", "s4"]


def test_chrome_export_round_trips_schema_valid(fresh_obs):
    with obs.span("outer", M=64):
        with obs.span("inner"):
            pass
    doc = json.loads(json.dumps(fresh_obs.tracer.export_chrome()))
    assert validate_chrome_trace(doc) is None
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} >= {"outer", "inner"}
    outer = next(e for e in xs if e["name"] == "outer")
    inner = next(e for e in xs if e["name"] == "inner")
    assert outer["args"]["M"] == 64
    # containment: inner nests inside outer on the same track
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_validate_chrome_trace_flags_violations():
    assert validate_chrome_trace({"not": "a trace"}) is not None
    bad = {"traceEvents": [{"ph": "X", "name": "a"}]}  # no ts/dur/pid/tid
    assert validate_chrome_trace(bad) is not None


# ---------------------------------------------------------------------------
# Dispatch telemetry
# ---------------------------------------------------------------------------


def test_record_hooks_are_noops_without_a_session(no_obs):
    record_kernel_dispatch("tiled", D=8, M=256, state_rows=8,
                           windowed=False, tile_m=128, vmem_bytes=1 << 20)
    record_chunk("jnp", B=2, chunk=4, M=64)  # must not raise
    record_shortlist("whole_pool")
    record_shortlist("top_k")
    record_sharded_merges(20, windowed=True)
    assert obs.registry() is None


def test_record_kernel_dispatch_counts_modes(fresh_obs):
    reg = fresh_obs.registry
    record_kernel_dispatch("resident", D=8, M=128, state_rows=8,
                           windowed=False, tile_m=128, vmem_bytes=4096)
    record_kernel_dispatch("tiled", D=8, M=4096, state_rows=8,
                           windowed=True, tile_m=512, vmem_bytes=8192)
    c = reg.get("dpp_kernel_dispatch_total")
    assert c.value(mode="resident", windowed="False") == 1
    assert c.value(mode="tiled", windowed="True") == 1
    assert reg.get("dpp_tile_m").value() == 512  # the last dispatch
    assert reg.get("dpp_vmem_bytes_est").value() == 8192


def test_rerank_emits_dispatch_and_eval_counters(fresh_obs):
    rr = session(slots=2, chunk=3, bucket=32, k=6)
    req = make_request(11, 40, k=6)
    rr.rerank(req)  # whole-slate path: dispatch + unchunked step counts
    for c, _ in rr.stream(req):  # chunked path: per-chunk launches
        c.block_until_ready()
    snap = fresh_obs.registry.snapshot()
    assert sum(snap["counters"]["greedy_dispatch_total"].values()) >= 1
    assert sum(snap["counters"]["greedy_chunks_total"].values()) >= 2
    steps = sum(snap["counters"]["greedy_steps_total"].values())
    evals = sum(snap["counters"]["marginal_evals_total"].values())
    assert steps >= 12  # 6 whole-slate + 6 streamed
    assert evals >= steps  # every launched step scores >= 1 candidate


# ---------------------------------------------------------------------------
# Front door: Reranker.rerank's phase spans and call counter
# ---------------------------------------------------------------------------

PHASES = ["serving.rerank.shortlist", "serving.rerank.greedy"]
CALLS = 2


def _phase_request(path):
    """A request down ``path``: ``single`` scores (M,), ``batched``
    (B, M), ``masked`` (B, M) with a per-user mask, ``sharded`` (M,) on
    every device of a mesh."""
    import jax
    from jax.sharding import AxisType

    from repro.serving import DPPRerankConfig, Reranker, RerankRequest

    rng = np.random.default_rng(3)
    M, D, B = 64, 8, 3
    feats = rng.normal(size=(M, D)).astype(np.float32)
    batched = path in ("batched", "masked")
    scores = rng.uniform(0.1, 1.0, size=(B, M) if batched else M)
    mask = rng.uniform(size=(B, M)) > 0.25 if path == "masked" else None
    mesh = None
    if path == "sharded":
        mesh = jax.make_mesh((jax.device_count(),), ("data",),
                             axis_types=(AxisType.Auto,))
    rr = Reranker(DPPRerankConfig(slate_size=5, shortlist=32, alpha=3.0,
                                  mesh=mesh))
    req = RerankRequest(
        scores=jnp.asarray(scores, jnp.float32), feats=jnp.asarray(feats),
        mask=None if mask is None else jnp.asarray(mask))
    return rr, req


def _drive(path):
    """``CALLS`` rerank calls down ``path`` in a fresh obs session: the
    front door's spans as ``[name, start_us, end_us]`` and the series of
    ``serving_rerank_calls_total``."""
    rr, req = _phase_request(path)
    obs.disable()
    s = obs.enable(ObsConfig(enabled=True))
    try:
        for _ in range(CALLS):
            np.asarray(rr.rerank(req)[0])
        spans = [[sp["name"], sp["start_us"], sp["start_us"] + sp["dur_us"]]
                 for sp in s.tracer.finished()
                 if sp["name"].startswith("serving.rerank")]
        calls = s.registry.snapshot()["counters"].get(
            "serving_rerank_calls_total", {})
        return {"spans": spans, "calls": calls}
    finally:
        obs.disable()


def _drive_on_four_devices(path):
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
        import json
        import jax
        assert jax.device_count() == 4, jax.devices()
        from tests.test_obs import _drive
        print(json.dumps(_drive({path!r})))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("path", ["single", "batched", "masked", "sharded"])
def test_rerank_records_phase_spans_and_counts_calls(path):
    """Each call records ``serving.rerank`` holding exactly its two
    phases, shortlist then greedy, and counts itself once under its
    path; the sharded path on a mesh of 4 devices."""
    out = (_drive_on_four_devices(path) if path == "sharded"
           else _drive(path))
    spans = sorted(out["spans"], key=lambda sp: sp[1])
    outer = [sp for sp in spans if sp[0] == "serving.rerank"]
    assert len(outer) == CALLS and len(spans) == 3 * CALLS
    for _, start, end in outer:
        inner = [sp[0] for sp in spans if sp[0] != "serving.rerank"
                 and start <= sp[1] and sp[2] <= end]
        assert inner == PHASES
    label = {"masked": "batched"}.get(path, path)
    assert out["calls"] == {f"path={label}": CALLS}


@pytest.mark.parametrize("path", ["single", "batched", "masked"])
def test_rerank_with_obs_off_records_nothing(no_obs, monkeypatch, path):
    """Off, every span the front door asks for is the shared no-op (no
    ``Span`` is built) and no registry exists to count into."""
    from repro.obs import trace

    rr, req = _phase_request(path)
    handed = []
    real_span = obs.span

    def spy(name, **attrs):
        handed.append(real_span(name, **attrs))
        return handed[-1]

    def no_span(*a, **k):
        raise AssertionError("a Span was allocated with obs off")

    monkeypatch.setattr(obs, "span", spy)
    monkeypatch.setattr(trace.Span, "__init__", no_span)
    for _ in range(CALLS):
        np.asarray(rr.rerank(req)[0])
    assert len(handed) == 3 * CALLS
    assert all(sp is NULL_SPAN for sp in handed)
    assert obs.registry() is None and obs.tracer() is None


def test_shortlist_counter_counts_each_path(fresh_obs):
    """``serving_shortlist_total`` counts one ``whole_pool`` per build
    that covers the pool and one ``top_k`` per build that sorts."""
    from repro.serving import DPPRerankConfig, Reranker, RerankRequest

    rng = np.random.default_rng(5)
    M = 48
    feats = jnp.asarray(rng.normal(size=(M, 8)), jnp.float32)
    scores = jnp.asarray(rng.uniform(0.1, 1.0, size=M), jnp.float32)
    req = RerankRequest(scores=scores, feats=feats)
    whole = Reranker(DPPRerankConfig(slate_size=4, shortlist=M, alpha=3.0))
    top = Reranker(DPPRerankConfig(slate_size=4, shortlist=16, alpha=3.0))
    for _ in range(3):
        np.asarray(whole.rerank(req)[0])
    for _ in range(2):
        np.asarray(top.rerank(req)[0])
    # a batched call builds its users' shortlists in one vmapped trace
    batch = RerankRequest(scores=jnp.stack([scores, scores[::-1]]),
                          feats=feats)
    np.asarray(whole.rerank(batch)[0])
    snap = fresh_obs.registry.snapshot()["counters"]
    assert snap["serving_shortlist_total"] == {
        "path=whole_pool": 4, "path=top_k": 2}
    assert snap["serving_rerank_calls_total"] == {
        "path=single": 5, "path=batched": 1}


def test_sharded_merges_count_k_per_kind_per_mesh_call(fresh_obs):
    """``sharded_merges_total`` counts k merges of each kind per mesh
    call (a batch of users once), ``chunk`` per streamed chunk,
    ``window`` only windowed, and nothing off the mesh."""
    import jax
    from jax.sharding import AxisType

    from repro.serving import DPPRerankConfig, Reranker, RerankRequest

    rng = np.random.default_rng(9)
    M, k = 48, 6
    feats = jnp.asarray(rng.normal(size=(M, 8)), jnp.float32)
    scores = jnp.asarray(rng.uniform(0.1, 1.0, size=M), jnp.float32)
    req = RerankRequest(scores=scores, feats=feats)
    batch = RerankRequest(scores=jnp.stack([scores, scores[::-1]]),
                          feats=feats)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])

    def merges():
        snap = fresh_obs.registry.snapshot()["counters"]
        return snap.get("sharded_merges_total", {})

    one_chip = Reranker(DPPRerankConfig(slate_size=k, shortlist=M))
    np.asarray(one_chip.rerank(req)[0])
    np.asarray(one_chip.rerank(batch)[0])
    assert merges() == {}
    exact = Reranker(DPPRerankConfig(slate_size=k, shortlist=M, mesh=mesh,
                                     chunk_size=4))
    for r in (req, req, batch):
        np.asarray(exact.rerank(r)[0])
    assert merges() == {"kind=argmax": 3 * k, "kind=winner": 3 * k}
    for c, _ in exact.stream(req):  # chunks of 4 and 2
        np.asarray(c)
    assert merges() == {"kind=argmax": 4 * k, "kind=winner": 4 * k}
    windowed = Reranker(DPPRerankConfig(slate_size=k, shortlist=M,
                                        mesh=mesh, window=2))
    np.asarray(windowed.rerank(req)[0])
    assert merges() == {"kind=argmax": 5 * k, "kind=winner": 5 * k,
                        "kind=window": k}


# ---------------------------------------------------------------------------
# Router integration: spans, stats view, hook guard, recompile ledger
# ---------------------------------------------------------------------------


def test_router_parity_and_pump_spans_with_obs_enabled(fresh_obs):
    rr = session(slots=2, chunk=3, bucket=32, k=8)
    reqs = [make_request(1, 40, k=8), make_request(2, 24, k=5),
            make_request(3, 48, k=7, masked=True)]
    expect = [tuple(np.asarray(x) for x in rr.rerank(r)) for r in reqs]
    handles = [rr.submit(r) for r in reqs]
    rr.router.drain()
    for h, (ei, ed) in zip(handles, expect):
        gi, gd = h.result()
        np.testing.assert_array_equal(gi, ei)
        np.testing.assert_allclose(gd, ed, rtol=1e-4, atol=1e-6)

    spans = fresh_obs.tracer.finished()
    counts = {}
    for s in spans:
        counts[s["name"]] = counts.get(s["name"], 0) + 1
    pumps = counts.get("router.pump", 0)
    assert pumps > 0
    for phase in ("evict", "admit", "launch", "materialize"):
        assert counts.get(f"router.pump.{phase}", 0) == pumps
    assert counts.get("router.pump.sync", 0) >= pumps - 1

    st = rr.router.stats  # the registry-backed view keeps its surface
    assert st.completed == 3 and st.slot_occupancy == 0
    assert st.ttfc_count == 3 and st.mean_ttfc > 0


def test_raising_metrics_hook_never_kills_the_pump(fresh_obs, caplog):
    calls = []

    def bad_hook(snap):
        calls.append(snap.completed)
        raise RuntimeError("operator bug")

    from repro.serving import DPPRerankConfig, Reranker, RouterConfig

    cfg = DPPRerankConfig(slate_size=6, shortlist=32, alpha=3.0,
                          chunk_size=3)
    rr = Reranker(cfg, router_config=RouterConfig(
        slots=2, chunk_size=3, max_candidates=32, metrics_hook=bad_hook,
    ))
    reqs = [make_request(7, 32, k=6), make_request(8, 24, k=4)]
    handles = [rr.submit(r) for r in reqs]
    with caplog.at_level("ERROR", logger="repro.serving.router"):
        rr.router.drain()
    assert all(h.done and not h.timed_out for h in handles)
    assert len(calls) > 0  # the hook kept being offered every pump
    assert any("metrics_hook" in r.message for r in caplog.records)
    errs = fresh_obs.registry.get("router_hook_errors_total")
    assert errs.total() == len(calls)


def test_router_zero_misses_vs_serial_per_k_recompiles(fresh_obs):
    """The fig8 gate at test size: the warmed router's measured drive
    shows zero jit cache misses, while per-k serial streaming (k folded
    into the compiled C (M, k) geometry) must miss per distinct k."""
    cm = fresh_obs.compile_monitor
    rr = session(slots=2, chunk=3, bucket=32, k=8, max_queue=16)
    reqs = [make_request(s, 36, k=kk, masked=s % 2 == 0)
            for s, kk in [(21, 8), (22, 5), (23, 7), (24, 4), (25, 6)]]
    warm = [rr.submit(r) for r in reqs[:2]]
    rr.router.drain()
    assert all(h.done for h in warm)
    cm.mark()
    handles = [rr.submit(r) for r in reqs[2:]]
    rr.router.drain()
    assert all(h.done for h in handles)
    assert cm.since_mark() == 0, (
        "router re-jitted after warmup — per-request k/mask leaked into "
        "compiled shapes"
    )

    cm.mark()
    distinct_k = sorted({r.slate_size for r in reqs})
    for k in distinct_k:
        r = reqs[[q.slate_size for q in reqs].index(k)]
        for c, _ in rr.stream(r):
            c.block_until_ready()
    assert cm.since_mark() >= len(distinct_k)
