"""The serving front door: one session object, one request object.

``Reranker(cfg)`` replaces the six-way function surface the serving
layer grew across PRs 1-5 (``rerank``, ``rerank_batch``,
``rerank_stream``, ``sharded_rerank``, ``sharded_rerank_stream``, plus
per-driver glue).  One session holds the model-side configuration — the
knobs that shape compiled computations (window, eps, backend, mesh,
tile_m, chunk_size, alpha) — and every call supplies a
:class:`RerankRequest` carrying the request-side knobs (slate length,
shortlist width, candidate mask, deadline).  The split is what lets the
continuous-batching router (``repro.serving.router``) vary k and mask
per live request without ever re-jitting: request knobs live in data
and host-side loop bounds, never in compiled statics.

Dispatch is by configuration and request shape, not by function name:

* ``cfg.mesh`` set          -> candidate-sharded SPMD paths;
* ``scores (B, M)``         -> the whole user batch on one mesh
                               (or a vmap of the single-device path);
* ``cfg.use_kernel``        -> Pallas kernels;
* otherwise                 -> the jnp reference path.

Methods::

    out = rr.rerank(req)              # whole slate(s), blocking
    for ids, dh in rr.stream(req):    # chunk-by-chunk emission
    handle = rr.submit(req)           # continuous-batching router
    handle.result()

``stream`` prepares eagerly: validation, the top-C shortlist, the
greedy state, and the kernel-operand padding all happen at call time —
once, O(M) — and each generator resume does only O(chunk) host-side
work (the previous serving generator re-entered validation per resume
and deferred the shortlist to the first ``next()``).

The legacy functions survived one release as ``DeprecationWarning``
shims and are now removed — this module is the only serving surface.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.dispatch import greedy_map, record_greedy_call
from repro.obs.dispatch import record_rerank_call, record_rerank_trace
from repro.serving.reranker import (
    DPPRerankConfig,
    _build_shortlist,
    _shortlist_kernel,
    _shortlist_width,
)


@dataclasses.dataclass(frozen=True)
class RerankRequest:
    """One rerank request: the data plus the request-side knobs.

    ``scores`` is ``(M,)`` (single) or ``(B, M)`` (user batch);
    ``feats`` is ``(M, D)`` — shared across a batch — or per-user
    ``(B, M, D)``.  ``slate_size`` / ``shortlist`` default to the
    session config's values; ``mask`` (``(M,)`` or ``(B, M)``) marks
    selectable candidates; ``deadline`` is a per-request latency budget
    in seconds, honoured by the router (timeout eviction returns the
    partial slate with ``timed_out=True``).  ``rid`` is an opaque
    caller tag echoed back on router handles.

    Validates at construction, like ``GreedySpec`` — a nonsensical
    request raises ``ValueError`` when it is built, not as a shape
    error inside a jitted serve step.
    """

    scores: Any
    feats: Any
    slate_size: Optional[int] = None
    shortlist: Optional[int] = None
    mask: Optional[Any] = None
    deadline: Optional[float] = None
    rid: Optional[Any] = None

    def __post_init__(self):
        if self.slate_size is not None and self.slate_size <= 0:
            raise ValueError(
                f"slate_size must be >= 1, got {self.slate_size}"
            )
        if self.shortlist is not None and self.shortlist <= 0:
            raise ValueError(f"shortlist must be >= 1, got {self.shortlist}")
        if self.deadline is not None and not self.deadline > 0:
            raise ValueError(
                f"deadline must be a positive seconds budget, got "
                f"{self.deadline}"
            )
        s_nd, f_nd = jnp.ndim(self.scores), jnp.ndim(self.feats)
        if s_nd not in (1, 2):
            raise ValueError(
                f"scores must be (M,) or a user batch (B, M), got "
                f"ndim={s_nd}"
            )
        if f_nd != 2 and not (s_nd == 2 and f_nd == 3):
            raise ValueError(
                f"feats must be (M, D) (shared) or, with batched scores, "
                f"per-user (B, M, D); got feats ndim={f_nd} with scores "
                f"ndim={s_nd}"
            )
        if self.mask is not None:
            m_nd = jnp.ndim(self.mask)
            if m_nd != 1 and not (s_nd == 2 and m_nd == 2):
                raise ValueError(
                    f"mask must be (M,) (shared) or, with batched scores, "
                    f"per-user (B, M); got mask ndim={m_nd} with scores "
                    f"ndim={s_nd}"
                )
        # one shared candidate axis M (and batch axis B) across all three
        # operands — caught here, at construction, instead of surfacing as
        # a shape error deep inside a jitted serve step
        M = jnp.shape(self.scores)[-1]
        f_shape = jnp.shape(self.feats)
        if f_shape[-2] != M:
            raise ValueError(
                f"scores and feats disagree on the candidate count: scores "
                f"carry M={M} candidates but feats "
                f"{tuple(f_shape)} carry {f_shape[-2]} — every operand "
                f"must share one M axis"
            )
        if s_nd == 2 and f_nd == 3 and f_shape[0] != jnp.shape(self.scores)[0]:
            raise ValueError(
                f"scores and feats disagree on the user batch: scores "
                f"carry B={jnp.shape(self.scores)[0]} users but feats "
                f"{tuple(f_shape)} carry {f_shape[0]}"
            )
        if self.mask is not None:
            m_shape = jnp.shape(self.mask)
            if m_shape[-1] != M:
                raise ValueError(
                    f"scores and mask disagree on the candidate count: "
                    f"scores carry M={M} candidates but mask "
                    f"{tuple(m_shape)} carries {m_shape[-1]} — every "
                    f"operand must share one M axis"
                )
            if len(m_shape) == 2 and m_shape[0] != jnp.shape(self.scores)[0]:
                raise ValueError(
                    f"scores and mask disagree on the user batch: scores "
                    f"carry B={jnp.shape(self.scores)[0]} users but mask "
                    f"{tuple(m_shape)} carries {m_shape[0]}"
                )

    @property
    def batched(self) -> bool:
        return jnp.ndim(self.scores) == 2

    @property
    def num_candidates(self) -> int:
        return jnp.shape(self.scores)[-1]


class Reranker:
    """A DPP rerank serving session.

    Holds one model-side :class:`DPPRerankConfig` and serves any number
    of :class:`RerankRequest`\\ s through three verbs — ``rerank``
    (whole slate, blocking), ``stream`` (chunk-emitting generator) and
    ``submit`` (continuous-batching router handle).  The compiled
    computations are keyed by the session config plus request *shapes*;
    request-side knobs (k, shortlist, mask, deadline) never force a
    recompile.
    """

    def __init__(self, cfg: DPPRerankConfig, router_config=None,
                 session_config=None):
        if not isinstance(cfg, DPPRerankConfig):
            raise TypeError(
                f"Reranker takes a DPPRerankConfig, got {type(cfg).__name__}"
            )
        self.cfg = cfg
        self._router_config = router_config
        self._router = None
        self._session_config = session_config
        self._sessions = None
        if cfg.obs is not None:  # enabled=False configs are a no-op
            obs.enable(cfg.obs)

    # -- request-side resolution -------------------------------------------

    def _cfg_for(self, req: RerankRequest) -> DPPRerankConfig:
        """The effective config for one request: the session's
        model-side knobs with the request's k / shortlist folded in."""
        k = req.slate_size if req.slate_size is not None else self.cfg.slate_size
        c = req.shortlist if req.shortlist is not None else self.cfg.shortlist
        if (k, c) == (self.cfg.slate_size, self.cfg.shortlist):
            return self.cfg
        return dataclasses.replace(self.cfg, slate_size=k, shortlist=c)

    @staticmethod
    def _as_request(req, kwargs) -> RerankRequest:
        if isinstance(req, RerankRequest):
            if kwargs:
                raise TypeError(
                    "pass request knobs inside the RerankRequest, not as "
                    f"keyword overrides: {sorted(kwargs)}"
                )
            return req
        raise TypeError(
            f"expected a RerankRequest, got {type(req).__name__}; build one "
            f"with RerankRequest(scores=..., feats=..., ...)"
        )

    # -- whole-slate -------------------------------------------------------

    def rerank(self, req: RerankRequest, **kwargs):
        """Whole-slate rerank: ``(indices, d_hist)``, shapes ``(N,)``
        single / ``(B, N)`` batched, global ids into the request's M
        (-1 after an eps-stop).  Dispatch: ``cfg.mesh`` -> sharded;
        batched scores -> the whole batch on the mesh, or a vmap of
        the single-device path."""
        req = self._as_request(req, kwargs)
        cfg = self._cfg_for(req)
        with obs.span(
            "serving.rerank", M=req.num_candidates, k=cfg.slate_size,
            batched=req.batched,
        ):
            record_rerank_call("sharded" if cfg.mesh is not None
                               else "batched" if req.batched else "single")
            if cfg.mesh is not None:
                from repro.serving.sharded_rerank import _sharded_kernel

                return _sharded_rerank_impl(
                    req.scores, req.feats, cfg, req.mask, _sharded_kernel
                )
            if req.batched:
                return _rerank_batch_impl(
                    req.scores, req.feats, cfg, req.mask
                )
            return _rerank_impl(req.scores, req.feats, cfg, req.mask)

    # -- chunked streaming -------------------------------------------------

    def stream(
        self, req: RerankRequest, chunk_size: Optional[int] = None, **kwargs
    ) -> Iterator[Tuple[jnp.ndarray, jnp.ndarray]]:
        """Stream one request's slate as it is selected.

        Returns a generator of ``(indices (c,) int32 global ids,
        d_hist (c,))`` chunks whose concatenation is a prefix of
        ``rerank(req)`` (same shortlist, same greedy sequence) covering
        every real selection; the last chunk is short when ``chunk``
        does not divide the slate, and once an eps-stop surfaces (a -1
        tail slot) the generator ends instead of launching further
        all--1 chunks.  ``chunk_size`` overrides ``cfg.chunk_size``.

        Preparation — validation, the top-C shortlist, the resumable
        greedy state, the kernel-operand padding — happens *here*, not
        at the first ``next()``: the returned generator's resume path
        costs O(chunk) host-side, nothing O(M).
        """
        req = self._as_request(req, kwargs)
        cfg = self._cfg_for(req)
        if req.batched:
            raise ValueError(
                "stream serves a single request (scores (M,)); batch "
                "serving goes through rerank or the router"
            )
        from repro.core.streaming import (
            greedy_chunk,
            greedy_init,
            resolve_chunk,
            slot_pad_v,
        )

        spec = cfg.greedy_spec()
        chunk = resolve_chunk(
            spec, chunk_size if chunk_size is not None else cfg.chunk_size
        )
        with obs.span(
            "serving.stream.prep", M=req.num_candidates, k=cfg.slate_size,
            chunk=chunk,
        ):
            if cfg.mesh is not None:
                from repro.serving.sharded_rerank import _sharded_kernel

                V, m_sel = _sharded_kernel(
                    req.scores, req.feats, cfg, req.mask
                )
                top_i = None
            else:
                V, m_sel, top_i = _shortlist_kernel(
                    req.scores, req.feats, cfg, req.mask
                )
            state = greedy_init(spec, V=V, mask=m_sel)
            V = slot_pad_v(spec, V, state)

        def emit():
            done, st = 0, state
            while done < cfg.slate_size:
                c = min(chunk, cfg.slate_size - done)
                with obs.span("serving.stream.chunk", chunk=c, done=done):
                    st, sel, dh = greedy_chunk(spec, st, V=V, chunk_size=c)
                    if top_i is not None:
                        sel = jnp.where(sel >= 0, top_i[jnp.clip(sel, 0)], -1)
                sel = sel.astype(jnp.int32)
                yield sel, dh
                done += c
                # eps-stop latch: once a chunk's tail slot is -1 the state
                # is stopped and every further chunk would be a dead
                # dispatch emitting all -1s.  The yielded chunk is already
                # materialized host-side by the consumer's inspection of
                # it, so reading its last slot costs no extra device sync.
                if done < cfg.slate_size and int(sel.reshape(-1)[-1]) < 0:
                    break

        return emit()

    # -- session-aware incremental rerank ----------------------------------

    @property
    def sessions(self):
        """The session store (created lazily on first use; see
        ``repro.serving.session``): per-user windowed greedy states kept
        device-resident between scroll events under an LRU byte budget."""
        if self._sessions is None:
            from repro.serving.session import SessionConfig, SessionStore

            self._sessions = SessionStore(
                self.cfg, self._session_config or SessionConfig()
            )
        return self._sessions

    def session(self, req: RerankRequest, sid=None, **kwargs):
        """Open a :class:`~repro.serving.session.RerankSession` over one
        request's shortlist: ``next_chunk(n)`` emits the next ``n``
        items conditioned on everything the session has already shown
        (never replaying selected steps), ``extend`` / ``rescore``
        delta-update the candidate pool in O(w * dM), and the store
        evicts cold sessions to ``session_config.budget_bytes``
        (transparently rebuilt on the next touch).  ``sid`` names the
        session (auto-assigned when None); calling again with an
        existing ``sid`` resumes that session and ignores ``req``.
        Requires a windowed config (``cfg.window < slate_size``);
        single requests only.
        """
        req = self._as_request(req, kwargs)
        if sid is not None and sid in self.sessions:
            return self.sessions.get(sid)
        return self.sessions.create(req, sid=sid, cfg=self._cfg_for(req))

    # -- continuous batching -----------------------------------------------

    @property
    def router(self):
        """The session's continuous-batching router (created lazily on
        first use; see ``repro.serving.router``)."""
        if self._router is None:
            from repro.serving.router import RerankRouter, RouterConfig

            self._router = RerankRouter(
                self.cfg, self._router_config or RouterConfig()
            )
        return self._router

    def submit(self, req: RerankRequest, **kwargs):
        """Submit one request to the session's continuous-batching
        router; returns a ``SlateHandle`` immediately.  The request
        joins the shared micro-batch at the next free slot — call
        ``handle.result()`` (or pump the router) to drive it."""
        req = self._as_request(req, kwargs)
        return self.router.submit(req)


# ---------------------------------------------------------------------------
# Implementation bodies (module-level so every Reranker session shares
# the same jit caches)
# ---------------------------------------------------------------------------


def _rerank_impl(scores, feats, cfg, mask):
    """``Reranker.rerank`` off the mesh, for one request (scores
    ``(M,)``) or a user batch (``(B, M)``): two cached compiled
    programs, the shortlist's and the greedy's, each dispatched inside
    its phase span.

    A program is keyed by the config's fields that shape it and the
    request's shapes, so a call after the first of its kind traces
    nothing.  Its body records only while it is traced, so the counters
    are recorded here, on the host, once per call, from static shapes;
    among them the kernel's mode and tile."""
    shape = jnp.shape(scores)
    spec = cfg.greedy_spec()
    C = _shortlist_width(cfg, shape[-1])
    record_greedy_call(spec, B=shape[0] if len(shape) == 2 else 1,
                       D=jnp.shape(feats)[-1], M=C)
    with obs.span("serving.rerank.shortlist"):
        V, m_top, top_i = _shortlist_program(
            scores, feats, mask, C=C, alpha=cfg.alpha
        )
    with obs.span("serving.rerank.greedy"):
        return _greedy_program(V, m_top, top_i, spec=spec)


def _rerank_batch_impl(scores, feats, cfg, mask):
    """The batched path: ``_rerank_impl`` over a user batch, under a
    name of its own so a test can stand in for the batched path alone."""
    return _rerank_impl(scores, feats, cfg, mask)


@functools.partial(jax.jit, static_argnames=("C", "alpha"))
def _shortlist_program(scores, feats, mask, *, C, alpha):
    """V, the shortlist mask and the shortlist's global ids; a user
    batch maps the single request's build over its users, with shared
    (M, D) or per-user (B, M, D) features and a shared (M,) or per-user
    (B, M) mask."""
    batched = scores.ndim == 2
    record_rerank_trace("batched" if batched else "single", "shortlist")
    build = functools.partial(_build_shortlist, C=C, alpha=alpha)
    if not batched:
        return build(scores, feats, mask)
    if mask is not None and mask.ndim == 1:
        mask = jnp.broadcast_to(mask, scores.shape)
    return jax.vmap(build, in_axes=(0, 0 if feats.ndim == 3 else None, 0))(
        scores, feats, mask
    )


@functools.partial(jax.jit, static_argnames=("spec",))
def _greedy_program(V, mask, top_i, *, spec):
    """``greedy_map`` over the shortlist's V and the picks mapped to
    global ids (-1 after an eps-stop), per user of a batch."""
    batched = V.ndim == 3
    record_rerank_trace("batched" if batched else "single", "greedy")
    pick = functools.partial(_pick, spec)
    with obs.muted():  # the host records this call's dispatch
        if batched:
            return jax.vmap(pick)(V, mask, top_i)
        return pick(V, mask, top_i)


def _pick(spec, V, mask, top_i):
    res = greedy_map(spec, V=V, mask=mask)
    sel = res.indices
    out = jnp.where(sel >= 0, top_i[jnp.clip(sel, 0)], -1)
    return out.astype(jnp.int32), res.d_hist


def _sharded_rerank_impl(scores, feats, cfg, mask, sharded_kernel):
    from repro.core.sharded import dpp_greedy_sharded

    with obs.span("serving.rerank.shortlist"):
        V, smask = sharded_kernel(scores, feats, cfg, mask)
    with obs.span("serving.rerank.greedy"):
        res = dpp_greedy_sharded(
            V,
            cfg.slate_size,
            mesh=cfg.mesh,
            axis_name=cfg.axis_name,
            window=cfg.window,
            eps=cfg.eps,
            mask=smask,
            tile_m=cfg.tile_m,
        )
        return res.indices.astype(jnp.int32), res.d_hist
