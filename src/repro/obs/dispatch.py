"""Dispatch and compile telemetry.

Two halves:

* **Compile monitoring** — :class:`CompileMonitor` counts XLA backend
  compiles (jit cache misses) and tracing passes through
  ``jax.monitoring``: jax emits a
  ``/jax/core/compile/backend_compile_duration`` duration event for
  every computation it actually compiles and *nothing* for a cache
  hit, so ``jit_cache_misses_total`` is a direct observation, not an
  inference.  ``mark()`` / ``since_mark()`` bracket a warmup: "the
  router never re-jits" becomes ``since_mark() == 0`` after the slot
  geometry compiled once, while per-k serial streaming shows >= 1 miss
  per distinct k (the fig8 gate and the acceptance criterion).

  jax only exposes process-global listeners (and only a clear-all), so
  one forwarder pair is registered once per process and routes events
  to whichever monitor is currently installed (none -> no-op).

* **Dispatch recording** — small helpers the greedy dispatch layers
  call to count *which path actually ran*: the kernel execution mode
  ``ops.py`` picked (jnp / resident / tiled and the ``plan_tile``
  tile/VMEM numbers behind it), the backend ``greedy_map`` routed to,
  and the launched work in greedy steps and per-step marginal
  evaluations (each greedy step updates and argmaxes over M candidate
  marginals; lazy/stochastic greedy variants exist to shrink exactly
  this number, so it is recorded rather than inferred).  All helpers
  no-op (one global read) when observability is disabled, and consume
  only static shapes/config — they are safe inside traced code and
  count one dispatch per trace, not per device replay.

Metric names are documented in DESIGN.md §8.
"""
from __future__ import annotations

from typing import Optional

import repro.obs as _obs

# one process-global forwarder pair; jax.monitoring has no per-listener
# deregistration, so the active monitor is swapped under these instead
_ACTIVE_MONITOR: Optional["CompileMonitor"] = None
_LISTENERS_REGISTERED = False

_BACKEND_COMPILE = "backend_compile"
_TRACE = "jaxpr_trace"


def _forward_event(event: str, **kw) -> None:
    m = _ACTIVE_MONITOR
    if m is not None:
        m._on_event(event)


def _forward_duration(event: str, duration: float, **kw) -> None:
    m = _ACTIVE_MONITOR
    if m is not None:
        m._on_duration(event, duration)


def _ensure_listeners() -> None:
    global _LISTENERS_REGISTERED
    if _LISTENERS_REGISTERED:
        return
    import jax.monitoring

    jax.monitoring.register_event_listener(_forward_event)
    jax.monitoring.register_event_duration_secs_listener(_forward_duration)
    _LISTENERS_REGISTERED = True


class CompileMonitor:
    """Counts jit cache misses (XLA backend compiles) into a registry.

    Counters:

    * ``jit_cache_misses_total`` — backend compiles observed;
    * ``jit_compile_seconds_total`` — wall seconds spent in them;
    * ``jit_traces_total`` — jaxpr tracing passes (re-traces that hit
      the compile cache still show up here).
    """

    def __init__(self, registry):
        self.registry = registry
        self._misses = registry.counter(
            "jit_cache_misses_total",
            "XLA backend compiles observed via jax.monitoring "
            "(a cached jit call emits none)",
        )
        self._secs = registry.counter(
            "jit_compile_seconds_total", "wall seconds spent compiling"
        )
        self._traces = registry.counter(
            "jit_traces_total", "jaxpr tracing passes"
        )
        self._mark = 0.0

    def install(self) -> "CompileMonitor":
        global _ACTIVE_MONITOR
        _ensure_listeners()
        _ACTIVE_MONITOR = self
        return self

    def uninstall(self) -> None:
        global _ACTIVE_MONITOR
        if _ACTIVE_MONITOR is self:
            _ACTIVE_MONITOR = None

    def _on_event(self, event: str) -> None:
        pass  # compile facts arrive as duration events; nothing to count

    def _on_duration(self, event: str, duration: float) -> None:
        if _BACKEND_COMPILE in event:
            self._misses.inc()
            self._secs.inc(duration)
        elif _TRACE in event:
            self._traces.inc()

    # -- warmup bracketing ---------------------------------------------------

    def misses(self) -> float:
        return self._misses.value()

    def mark(self) -> None:
        """Remember the current miss count (call when warmup is done)."""
        self._mark = self._misses.value()

    def since_mark(self) -> float:
        """Misses since :meth:`mark` — 0 proves a serving loop ran
        entirely on cached computations."""
        return self._misses.value() - self._mark


# ---------------------------------------------------------------------------
# Dispatch recording (called by core/dispatch, core/streaming, core/sharded,
# kernel ops)
# ---------------------------------------------------------------------------


def record_rerank_call(path: str) -> None:
    """One ``Reranker.rerank`` call down ``path`` (``single`` /
    ``batched`` / ``sharded``): the count the per-call readers of the
    ``serving.rerank.*`` spans divide by."""
    reg = _obs.registry()
    if reg is None:
        return
    reg.counter(
        "serving_rerank_calls_total", "Reranker.rerank calls by path"
    ).inc(path=path)


def record_rerank_trace(path: str, phase: str) -> None:
    """One trace of ``Reranker.rerank``'s compiled ``phase`` program
    (``shortlist`` / ``greedy``) down ``path`` (``single`` /
    ``batched`` / ``sharded``).  Called inside the program's body, so it
    counts cache misses: ``serving_rerank_calls_total`` less this is how
    often a cached program served the call.  The mesh's V build serves
    ``stream`` and router admission too, at the request's own width, so
    their traces count under ``path=sharded, phase=shortlist`` as well."""
    reg = _obs.registry()
    if reg is None:
        return
    reg.counter(
        "serving_rerank_traces_total",
        "traces of Reranker.rerank's compiled phase programs",
    ).inc(path=path, phase=phase)


def record_shortlist(path: str) -> None:
    """One shortlist build down ``path``: ``whole_pool`` (the shortlist
    covers the pool, so V is built in id order with no sort or gather)
    or ``top_k`` (sort, then gather the shortlisted rows).  Counted in
    Python, on the host, once per build."""
    reg = _obs.registry()
    if reg is None:
        return
    reg.counter(
        "serving_shortlist_total", "shortlist builds by path"
    ).inc(path=path)


def record_kernel_dispatch(
    mode: str,
    *,
    D: int,
    M: int,
    state_rows: int,
    windowed: bool,
    tile_m: Optional[int] = None,
    vmem_bytes: Optional[int] = None,
    interpret: bool = False,
) -> None:
    """One kernel execution-mode decision: which kernel path won
    (``jnp`` / ``resident`` / ``tiled`` / ``fused_chunk``), whether the
    Pallas kernel runs interpreted, and the ``plan_tile`` numbers
    behind it."""
    reg = _obs.registry()
    if reg is None:
        return
    reg.counter(
        "dpp_kernel_dispatch_total", "kernel execution modes chosen by ops.py"
    ).inc(mode=mode, windowed=str(bool(windowed)))
    if interpret and mode != "jnp":
        reg.counter(
            "dpp_kernel_interpreted_total",
            "Pallas dispatches run by the interpreter (0 on a TPU)",
        ).inc(mode=mode)
    g = reg.gauge(
        "dpp_tile_m", "candidate-axis tile of the last tiled dispatch (0 = "
        "whole-M resident)"
    )
    g.set(0 if tile_m is None else tile_m)
    if vmem_bytes is not None:
        reg.gauge(
            "dpp_vmem_bytes_est",
            "VMEM working-set estimate of the last dispatch",
        ).set(vmem_bytes)


def record_sharded_merges(steps: int, *, windowed: bool) -> None:
    """The cross-chip merges of one mesh dispatch of ``steps`` greedy
    steps: per step one ``argmax`` (the all-gather of every shard's
    best), one ``winner`` (the psum that broadcasts the winner's
    column) and, windowed, one ``window`` (the psum that gathers the
    window factor).  A merge moves every user of a batch at once, so a
    batch counts once."""
    reg = _obs.registry()
    if reg is None:
        return
    c = reg.counter(
        "sharded_merges_total", "cross-chip merges of the mesh greedy by kind"
    )
    kinds = ("argmax", "winner", "window") if windowed else ("argmax", "winner")
    for kind in kinds:
        c.inc(steps, kind=kind)


def record_tile_resolution(source: str) -> None:
    """Where one dispatch's tile came from: the caller's ``explicit``
    int, or the VMEM ``model`` (``tiling.plan_tile``, in ``ops.py`` and,
    for a mesh's per-device tile, in ``core/sharded.py``)."""
    reg = _obs.registry()
    if reg is None:
        return
    reg.counter(
        "dpp_tile_source_total",
        "where each dispatch's tile came from (explicit/model)",
    ).inc(source=source)


def record_greedy_map(backend: str, *, B: int, k: int, M: int,
                      chunked: bool = False) -> None:
    """One whole-slate ``greedy_map`` dispatch.  Launched work (steps,
    marginal evaluations) is counted here for unchunked runs; chunked
    runs count it per chunk in :func:`record_chunk` instead."""
    reg = _obs.registry()
    if reg is None:
        return
    reg.counter(
        "greedy_dispatch_total", "greedy_map dispatches by backend"
    ).inc(backend=backend, chunked=str(bool(chunked)))
    if not chunked:
        _count_steps(reg, backend, B * k, B * k * M)


def record_chunk(backend: str, *, B: int, chunk: int, M: int) -> None:
    """One resumable chunk launch: ``B`` lanes x ``chunk`` greedy steps
    over ``M`` candidate columns."""
    reg = _obs.registry()
    if reg is None:
        return
    reg.counter(
        "greedy_chunks_total", "resumable chunk launches by backend"
    ).inc(backend=backend)
    _count_steps(reg, backend, B * chunk, B * chunk * M)


def record_session_delta(op: str, *, w: int, dm: int) -> None:
    """One session delta update (``extend`` / ``rescore`` / ``rebuild``):
    ``dm`` candidate columns re-solved against a ``w``-row window —
    O(w * dm) device work where a from-scratch rerank would pay
    O(k * M)."""
    reg = _obs.registry()
    if reg is None:
        return
    reg.counter(
        "session_deltas_total", "session delta updates by op"
    ).inc(op=op)
    reg.counter(
        "session_delta_cols_total",
        "candidate columns re-solved by session delta updates",
    ).inc(dm, op=op)


def record_session_evict(resident_bytes: int, *, evicted: int = 1) -> None:
    """``evicted`` sessions dropped to the LRU byte budget;
    ``resident_bytes`` is the store's device footprint *after* the
    eviction (also exported on every resume via
    :func:`record_session_resident`)."""
    reg = _obs.registry()
    if reg is None:
        return
    reg.counter(
        "session_evictions_total",
        "session states dropped by the LRU byte budget",
    ).inc(evicted)
    reg.gauge(
        "session_resident_bytes",
        "device bytes held by resident session states",
    ).set(resident_bytes)


def record_session_resident(resident_bytes: int, *, sessions: int) -> None:
    """Current store footprint: ``sessions`` resident states holding
    ``resident_bytes`` on device."""
    reg = _obs.registry()
    if reg is None:
        return
    reg.gauge(
        "session_resident_bytes",
        "device bytes held by resident session states",
    ).set(resident_bytes)
    reg.gauge(
        "session_resident_count", "resident session states"
    ).set(sessions)


def _count_steps(reg, backend: str, steps: int, evals: int) -> None:
    reg.counter(
        "greedy_steps_total", "greedy steps launched (padded/parked lanes "
        "included — this is device work, not delivered selections)"
    ).inc(steps, backend=backend)
    reg.counter(
        "marginal_evals_total", "candidate marginals evaluated: every "
        "launched step updates and argmaxes M candidate gains (the count "
        "lazy-greedy variants exist to shrink)"
    ).inc(evals, backend=backend)
