"""Session-aware incremental rerank: condition on shown items instead
of recomputing.

A feed session is a sequence of reranks over a drifting candidate pool.
The paper's §2.4 sliding-window semantics (repulsion only among the
last ``w`` shown items) means the windowed ``GreedyState`` — the
``(w, M)`` Cholesky ring plus the marginal gains ``d2`` — already *is*
the session's conditioning state: everything the next pick needs to
know about the items already shown.  So instead of replaying a full
greedy run from step 0 on every scroll event, this layer

* **resumes** — each session keeps its windowed state device-resident
  between scroll events; ``next_chunk(n)`` emits the next ``n`` items
  conditioned on the shown history, never replaying selected steps
  (O(n * w * M) device work, independent of how much was shown);
* **delta-updates** — when new candidates arrive (``extend``) or
  scores refresh (``rescore``), only the affected columns of the
  session's shortlisted ``V`` are written and only *their* ``C``
  columns / ``d2`` entries re-solved against the current window
  (``greedy_state_extend`` / ``greedy_state_rescore`` in
  ``repro.core.streaming`` — O(w * dM), never O(k * M));
* **evicts** — :class:`SessionStore` keeps every session under one LRU
  device-byte budget.  An evicted session is *not* lost: the windowed
  state is a pure function of the pool and the shown history (both
  mirrored on host), so the next touch rebuilds it bit-compatibly via
  ``repro.core.windowed.windowed_state_rebuild`` — one Cholesky +
  one triangular solve, transparent to the caller.

State ownership: the device arrays (``_state``, ``_V``) are owned by
the session and may vanish at any moment (eviction); the host mirrors
(pool vectors, raw features, global ids, shown history, dead set) are
authoritative and never evicted.  DESIGN.md §10 has the delta-update
math and the LRU contract.

Observability: spans ``serving.session.{resume,extend,rescore,
rebuild,evict}``; metrics ``session_evictions_total``,
``session_resident_bytes``, ``session_deltas_total`` (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
import itertools
from collections import OrderedDict
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.kernel_matrix import map_relevance
from repro.core.streaming import (
    GreedyState,
    greedy_chunk,
    greedy_init,
    greedy_state_extend,
    greedy_state_rescore,
    slot_pad_v,
)
from repro.core.windowed import windowed_state_rebuild
from repro.obs.dispatch import (
    record_session_delta,
    record_session_evict,
    record_session_resident,
)
from repro.serving.reranker import DPPRerankConfig, _shortlist_kernel


@dataclasses.dataclass(frozen=True)
class SessionConfig:
    """Store-side knobs.

    ``budget_bytes`` caps the *device* bytes held by resident session
    states across the store (LRU eviction; host mirrors are exempt —
    they are what makes eviction reversible).  ``capacity`` is each
    session's candidate-pool width in columns; extends append into the
    headroom above the initial shortlist.  Default: twice the
    shortlist, so a session can double its pool before exhausting.
    """

    budget_bytes: int = 64 << 20
    capacity: Optional[int] = None

    def __post_init__(self):
        if self.budget_bytes <= 0:
            raise ValueError(
                f"budget_bytes must be >= 1, got {self.budget_bytes}"
            )
        if self.capacity is not None and self.capacity <= 0:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")


def _check_session_cfg(cfg: DPPRerankConfig) -> None:
    if cfg.mesh is not None:
        raise NotImplementedError(
            "sessions over sharded pools are not implemented: the window "
            "ring lives sharded behind shard_map and a column delta "
            "crosses device boundaries.  Lands with the ROADMAP 'Router "
            "scale-up' item (sharded slot batches + window heterogeneity)."
        )
    if cfg.window is None or cfg.window >= cfg.slate_size:
        raise ValueError(
            f"sessions need a windowed config (window < slate_size): the "
            f"exact C (M, k) layout retains the whole selection history "
            f"instead of a w-item conditioning window, so shown items "
            f"cannot be conditioned on in O(w*M) — got window="
            f"{cfg.window}, slate_size={cfg.slate_size}"
        )


class SessionStore:
    """LRU store of :class:`RerankSession`\\ s under one device-byte
    budget.  Created lazily by ``Reranker.sessions``; sessions are
    opened with ``Reranker.session(req, sid=...)``."""

    def __init__(self, cfg: DPPRerankConfig, scfg: SessionConfig):
        _check_session_cfg(cfg)
        self.cfg = cfg
        self.scfg = scfg
        self._sessions: "OrderedDict[object, RerankSession]" = OrderedDict()
        self._ids = itertools.count()

    def __contains__(self, sid) -> bool:
        return sid in self._sessions

    def __len__(self) -> int:
        return len(self._sessions)

    def get(self, sid) -> "RerankSession":
        """The named session, touched to most-recently-used."""
        sess = self._sessions[sid]
        self._touch(sess)
        return sess

    def create(self, req, sid=None, cfg=None) -> "RerankSession":
        """Open a session over one request's shortlist."""
        cfg = cfg if cfg is not None else self.cfg
        _check_session_cfg(cfg)
        if sid is None:
            sid = next(self._ids)
        if sid in self._sessions:
            raise ValueError(
                f"session {sid!r} already exists — resume it with "
                f"Reranker.session(req, sid={sid!r}) / store.get, or "
                f"close it first"
            )
        sess = RerankSession(self, sid, cfg, req)
        self._sessions[sid] = sess
        self._balance(keep=sess)
        return sess

    def close(self, sid) -> None:
        """Drop a session entirely (device state and host mirrors)."""
        sess = self._sessions.pop(sid)
        sess._drop()
        record_session_resident(
            self.resident_bytes(), sessions=self._resident_count()
        )

    def resident_bytes(self) -> int:
        return sum(
            s._resident_bytes for s in self._sessions.values()
            if s._state is not None
        )

    def _resident_count(self) -> int:
        return sum(
            1 for s in self._sessions.values() if s._state is not None
        )

    def _touch(self, sess: "RerankSession") -> None:
        self._sessions.move_to_end(sess.sid)

    def _balance(self, keep: "RerankSession") -> None:
        """Evict least-recently-used resident sessions until the store
        fits ``budget_bytes``.  The session being served is never
        evicted, even when it alone exceeds the budget."""
        total = self.resident_bytes()
        for sess in list(self._sessions.values()):  # LRU order first
            if total <= self.scfg.budget_bytes:
                break
            if sess is keep or sess._state is None:
                continue
            freed = sess._resident_bytes
            with obs.span("serving.session.evict", sid=str(sess.sid),
                          bytes=freed):
                sess._drop()
            total -= freed
            record_session_evict(total)
        record_session_resident(total, sessions=self._resident_count())


class RerankSession:
    """One user's stateful diversified feed.

    Holds the windowed greedy state over a shortlisted, capacity-padded
    candidate pool.  Selections are reported as *global ids*: the
    request's original candidate indices for the initial shortlist,
    then the ids :meth:`extend` returns for appended candidates.
    """

    def __init__(self, store: SessionStore, sid, cfg: DPPRerankConfig, req):
        if req.batched:
            raise ValueError(
                "a session serves one user's feed (scores (M,)); open one "
                "session per user"
            )
        self.store = store
        self.sid = sid
        self.cfg = cfg
        self.spec = cfg.greedy_spec()
        self.w = min(cfg.window, cfg.slate_size)

        V, m_top, top_i = _shortlist_kernel(
            req.scores, req.feats, cfg, req.mask
        )
        D, C0 = V.shape
        cap = store.scfg.capacity or 2 * C0
        self.cap = max(cap, C0)
        self.D = D

        # host mirrors — authoritative, never evicted; what makes
        # device eviction reversible
        self._Vh = np.zeros((D, self.cap), np.asarray(V).dtype)
        self._Vh[:, :C0] = np.asarray(V)
        self._Fh = np.zeros((D, self.cap), np.asarray(req.feats).dtype)
        self._Fh[:, :C0] = np.asarray(req.feats)[np.asarray(top_i)].T
        self._gid = np.full((self.cap,), -1, np.int64)
        self._gid[:C0] = np.asarray(top_i)
        self._col_of = {int(g): i for i, g in enumerate(self._gid[:C0])}
        self._dead = np.ones((self.cap,), bool)
        self._dead[:C0] = (
            False if m_top is None else ~np.asarray(m_top)
        )
        self._shown: list[int] = []
        self._m_live = C0
        self._next_gid = int(req.num_candidates)
        self._stopped_h = False

        # device state — owned here, droppable by the store's LRU
        self._state: Optional[GreedyState] = None
        self._V = None
        self._resident_bytes = 0
        self._materialize()

    # -- device residency ---------------------------------------------------

    def _materialize(self) -> None:
        """(Re)build the device state from the host mirrors + history.

        Fresh sessions get the plain windowed init; touched-after-evict
        sessions additionally rebuild the ring rows from the last-w
        shown columns (unique Cholesky factor — bit-compatible with the
        state the incremental path reached, see
        ``windowed_state_rebuild``)."""
        Vp = jnp.asarray(self._Vh)
        st = greedy_init(self.spec, V=Vp, mask=jnp.asarray(~self._dead))
        Vop = slot_pad_v(self.spec, Vp, st)
        if self._shown:
            ring = self._shown[-self.w:]
            ring = ring + [-1] * (self.w - len(ring))
            Mp = st.d2.shape[-1]
            dead_p = np.ones((Mp,), bool)
            dead_p[: self.cap] = self._dead
            ring_j = jnp.asarray(ring, jnp.int32)
            C, d2 = windowed_state_rebuild(
                Vop, ring_j, jnp.asarray(dead_p)
            )
            batched = st.C.ndim == 3  # Pallas stream layout, B == 1
            st = GreedyState(
                jnp.asarray(len(self._shown), jnp.int32),
                jnp.full_like(st.stopped, self._stopped_h),
                C[None] if batched else C,
                d2[None] if batched else d2,
                ring_j[None] if st.win.ndim == 2 else ring_j,
            )
            record_session_delta("rebuild", w=self.w, dm=self.cap)
        self._state = st
        self._V = Vop
        self._resident_bytes = (
            sum(leaf.nbytes for leaf in st) + Vop.nbytes
        )

    def _ensure_resident(self) -> None:
        if self._state is None:
            with obs.span("serving.session.rebuild", sid=str(self.sid),
                          shown=len(self._shown)):
                self._materialize()
            self.store._balance(keep=self)

    def _drop(self) -> None:
        self._state = None
        self._V = None

    @property
    def resident(self) -> bool:
        return self._state is not None

    @property
    def shown(self) -> np.ndarray:
        """Global ids of everything this session has emitted, in order."""
        return self._gid[np.asarray(self._shown, np.int64)]

    # -- the three session verbs -------------------------------------------

    def next_chunk(self, n: Optional[int] = None):
        """Emit the next ``n`` feed items conditioned on the shown
        history: ``(ids (m,) int64 global ids, gains (m,))`` with
        ``m <= n`` — short exactly when the session eps-stops (no
        remaining candidate clears the gate; a later ``extend`` /
        ``rescore`` can revive it).  Never replays selected steps."""
        n = n if n is not None else self.cfg.chunk_size
        if n is None or n < 1:
            raise ValueError(
                f"next_chunk needs n >= 1 (or cfg.chunk_size set), got {n}"
            )
        if self._stopped_h:
            return (
                np.empty((0,), np.int64),
                np.empty((0,), self._Vh.dtype),
            )
        with obs.span("serving.session.resume", sid=str(self.sid), n=n,
                      shown=len(self._shown)):
            self.store._touch(self)
            self._ensure_resident()
            self._state, sel, dh = greedy_chunk(
                self.spec, self._state, V=self._V, chunk_size=n
            )
        sel_h = np.asarray(sel).reshape(-1)
        dh_h = np.asarray(dh).reshape(-1)
        live = sel_h >= 0
        cols = sel_h[live].astype(np.int64)
        self._shown.extend(int(c) for c in cols)
        self._dead[cols] = True
        if cols.size < n:
            self._stopped_h = True
        return self._gid[cols].copy(), dh_h[live].copy()

    def extend(self, scores, feats, mask=None) -> np.ndarray:
        """Append ``dM`` new candidates to the session's pool.

        ``scores (dM,)`` and ``feats (dM, D)`` enter the kernel exactly
        as the initial shortlist did (relevance-scaled columns, paper
        eq. 21); ``mask`` False keeps a column unselectable.  Only the
        new columns' Cholesky state is computed — O(w * dM) — and a
        stopped session is revived.  Returns the ``(dM,)`` global ids
        assigned to the new candidates."""
        scores = jnp.asarray(scores)
        feats = jnp.asarray(feats)
        if scores.ndim != 1 or feats.ndim != 2:
            raise ValueError(
                f"extend takes scores (dM,) and feats (dM, D), got "
                f"ndim={scores.ndim}/{feats.ndim}"
            )
        dm = scores.shape[0]
        if feats.shape != (dm, self.D):
            raise ValueError(
                f"extend feats must be ({dm}, {self.D}) to match the "
                f"session's pool, got {tuple(feats.shape)}"
            )
        start = self._m_live
        if start + dm > self.cap:
            raise ValueError(
                f"session pool exhausted: {start} columns used + {dm} new "
                f"> capacity {self.cap} — size SessionConfig.capacity for "
                f"the feed's total candidate churn"
            )
        with obs.span("serving.session.extend", sid=str(self.sid), dm=dm,
                      start=start):
            self.store._touch(self)
            self._ensure_resident()
            rel = map_relevance(scores.astype(jnp.float32), self.cfg.alpha)
            if mask is not None:
                rel = jnp.where(jnp.asarray(mask), rel, 0.0)
            V_blk = (feats * rel[:, None]).T
            mask_j = None if mask is None else jnp.asarray(mask)
            self._state, self._V = greedy_state_extend(
                self.spec, self._state, self._V, start, V_blk, mask_j
            )
        gids = np.arange(self._next_gid, self._next_gid + dm, dtype=np.int64)
        self._next_gid += dm
        self._gid[start:start + dm] = gids
        for i, g in enumerate(gids):
            self._col_of[int(g)] = start + i
        self._Vh[:, start:start + dm] = np.asarray(V_blk)
        self._Fh[:, start:start + dm] = np.asarray(feats).T
        self._dead[start:start + dm] = (
            False if mask is None else ~np.asarray(mask)
        )
        self._m_live = start + dm
        self._stopped_h = False
        record_session_delta("extend", w=self.w, dm=dm)
        return gids

    def rescore(self, ids, scores) -> None:
        """Refresh the relevance scores of existing candidates.

        ``ids (dM,)`` are global ids, ``scores (dM,)`` their new
        scores.  The affected columns are rewritten from the stored raw
        features and re-solved against the current window — already-
        shown (and masked) columns keep their exact old state bit-for-
        bit, so history is never rewritten; a stopped session is
        revived.  Cost is O(w * span) where span is the smallest
        contiguous pool range covering the touched columns."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        scores = np.asarray(scores).reshape(-1)
        if ids.shape != scores.shape:
            raise ValueError(
                f"rescore takes matching ids/scores, got {ids.shape} vs "
                f"{scores.shape}"
            )
        if ids.size == 0:
            return
        try:
            cols = np.array([self._col_of[int(g)] for g in ids])
        except KeyError as e:
            raise ValueError(
                f"rescore: unknown global id {e.args[0]} — ids must come "
                f"from the session's shortlist or from extend()"
            ) from None
        lo, hi = int(cols.min()), int(cols.max()) + 1
        with obs.span("serving.session.rescore", sid=str(self.sid),
                      dm=hi - lo):
            self.store._touch(self)
            self._ensure_resident()
            rel = np.asarray(
                map_relevance(jnp.asarray(scores, jnp.float32),
                              self.cfg.alpha)
            )
            Vb = self._Vh[:, lo:hi].copy()
            Vb[:, cols - lo] = self._Fh[:, cols] * rel[None, :]
            self._state, self._V = greedy_state_rescore(
                self.spec, self._state, self._V, lo, jnp.asarray(Vb)
            )
        live = ~self._dead[cols]
        self._Vh[:, cols[live]] = Vb[:, (cols - lo)[live]]
        self._stopped_h = False
        record_session_delta("rescore", w=self.w, dm=hi - lo)
