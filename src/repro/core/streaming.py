"""Step-resumable greedy MAP — the state/init/step/chunk layer under
streaming slate emission.

The paper's greedy loop is a pure recurrence on a small state (the
incremental Cholesky rows, the marginal gains ``d2`` and, windowed, the
ring order); the whole-slate entry points in ``greedy_chol`` /
``windowed`` just run it ``k`` times inside one ``fori_loop``.  This
module reifies that state as :class:`GreedyState` and exposes the
recurrence in resumable pieces:

* ``greedy_init(spec, L=|V=, mask=)``  -> initial state;
* ``greedy_step(spec, state, ...)``    -> one selection;
* ``greedy_chunk(spec, state, ...)``   -> ``chunk_size`` selections.

Chunks concatenate *exactly* (indices bitwise, d_hist to the last bit on
the jnp backend, ~1 ulp across kernels) to the whole-slate result,
because every backend's chunk executor runs the identical per-step op
sequence as its whole-slate loop:

* jnp       — ``greedy_step_exact`` / ``greedy_step_windowed``, the very
              functions the whole-slate ``fori_loop`` bodies call;
* pallas    — the fused multi-step chunk kernels
              (``repro.kernels.dpp_greedy.ops.dpp_greedy_stream_*``):
              one grid sweep per step, one ``pallas_call`` — one HBM
              C/d2 round-trip — per *chunk*;
* sharded   — per-device chunk bodies built from the same step factories
              as the whole-slate SPMD loop
              (``repro.core.sharded.dpp_greedy_sharded_stream_*``); the
              sharded state stays device-resident between chunks.

``GreedyState`` is **backend-specific and opaque**: the jnp exact state
keeps the paper's column layout ``C (M, k)``, the windowed state the
ring layout ``C (w, M)``, the Pallas state the kernels' padded row
layout, and the sharded state globally-shaped sharded arrays.  Always
thread a state back into the same ``spec`` (and kernel operand) that
created it.

The serving front door is ``repro.serving.Reranker.stream`` (and the
continuous-batching router over the slot substrate); the
dispatch-level generator is ``repro.core.dispatch.greedy_map_chunks``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.greedy_chol import NEG_INF, greedy_step_exact
from repro.core.windowed import greedy_step_windowed
from repro.obs.dispatch import record_chunk


def _backend_label(spec) -> str:
    if spec.sharded():
        return "sharded"
    return "pallas" if spec.backend == "pallas" else "jnp"


class GreedyState(NamedTuple):
    """Resumable greedy MAP state (backend-specific layouts, see module
    docstring).

    t:       () int32 — the next absolute step index.
    stopped: () bool  — eps-stop latch ((B,) for batched Pallas states).
    C:       Cholesky state — jnp exact ``(M, k)`` columns, windowed
             ``(w, M)`` ring rows; Pallas ``(B, R, Mp)``; sharded the
             global view of the per-device slices.
    d2:      marginal gains with the selectability mask folded in
             (masked candidates sit at -inf) — ``(M,)`` / ``(B, Mp)``.
    win:     window ring ids, oldest first (``(0,)``-shaped when exact).
    """

    t: jnp.ndarray
    stopped: jnp.ndarray
    C: jnp.ndarray
    d2: jnp.ndarray
    win: jnp.ndarray


def _check_kernel_args(spec, L, V):
    if (L is None) == (V is None):
        raise ValueError("pass exactly one of L= (dense) or V= (low-rank)")
    if L is not None and (spec.backend == "pallas" or spec.sharded()):
        raise ValueError(
            f"backend {spec.backend!r} streams the low-rank V only — a "
            f"dense L cannot be tiled or candidate-sharded"
        )


def resolve_chunk(spec, chunk_size: Optional[int]) -> int:
    """The effective chunk size: the explicit argument wins, else
    ``spec.chunk_size``; one of them must be set and positive."""
    c = chunk_size if chunk_size is not None else spec.chunk_size
    if c is None:
        raise ValueError(
            "no chunk size: pass chunk_size= or set GreedySpec.chunk_size"
        )
    if c < 1:
        raise ValueError(f"chunk_size must be >= 1, got {c}")
    return c


# ---------------------------------------------------------------------------
# jnp executors (single problem; dense L or low-rank V)
# ---------------------------------------------------------------------------


def _init_jnp(k: int, window: Optional[int], L, V, mask) -> GreedyState:
    kern = L if L is not None else V
    if kern.ndim != 2:
        raise ValueError(
            f"jnp streaming takes a single problem (L (M, M) / V (D, M)), "
            f"got ndim={kern.ndim}"
        )
    M = kern.shape[-1]
    dtype = kern.dtype
    if mask is None:
        mask = jnp.ones((M,), bool)
    diag = jnp.diagonal(L) if L is not None else jnp.sum(V * V, axis=0)
    d2 = jnp.where(mask, diag, NEG_INF)
    if window is not None and window < k:
        w = min(window, k)
        C = jnp.zeros((w, M), dtype)
        win = jnp.full((w,), -1, jnp.int32)
    else:
        C = jnp.zeros((M, k), dtype)
        win = jnp.zeros((0,), jnp.int32)
    return GreedyState(
        jnp.zeros((), jnp.int32), jnp.asarray(False), C, d2, win
    )


def _chunk_body(row_fn, state: GreedyState, chunk: int, eps: float):
    """``chunk`` steps of the shared per-step bodies, absolute step
    ``t = state.t + s`` — the same op sequence as the whole-slate loops."""
    dtype = state.d2.dtype
    eps2 = jnp.asarray(eps, dtype) ** 2
    tiny = jnp.asarray(1e-30, dtype)
    windowed = state.win.shape[0] > 0
    sel = jnp.full((chunk,), -1, jnp.int32)
    dh = jnp.zeros((chunk,), dtype)

    if windowed:
        w = state.C.shape[0]

        def body(s, carry):
            C, d2, win, stopped, sel, dh = carry
            C, d2, win, stopped, j, dj = greedy_step_windowed(
                row_fn, state.t + s, C, d2, win, stopped,
                w=w, eps2=eps2, tiny=tiny,
            )
            sel = sel.at[s].set(jnp.where(stopped, -1, j))
            dh = dh.at[s].set(jnp.where(stopped, 0.0, dj))
            return C, d2, win, stopped, sel, dh

        C, d2, win, stopped, sel, dh = jax.lax.fori_loop(
            0, chunk, body,
            (state.C, state.d2, state.win, state.stopped, sel, dh),
        )
    else:

        def body(s, carry):
            C, d2, stopped, sel, dh = carry
            C, d2, stopped, j, dj = greedy_step_exact(
                row_fn, state.t + s, C, d2, stopped, eps2
            )
            sel = sel.at[s].set(jnp.where(stopped, -1, j))
            dh = dh.at[s].set(jnp.where(stopped, 0.0, dj))
            return C, d2, stopped, sel, dh

        C, d2, stopped, sel, dh = jax.lax.fori_loop(
            0, chunk, body, (state.C, state.d2, state.stopped, sel, dh)
        )
        win = state.win
    next_state = GreedyState(state.t + chunk, stopped, C, d2, win)
    return next_state, sel, dh


@partial(jax.jit, static_argnames=("chunk", "eps"))
def _chunk_dense(L, state, chunk: int, eps: float):
    return _chunk_body(lambda j: L[j], state, chunk, eps)


@partial(jax.jit, static_argnames=("chunk", "eps"))
def _chunk_lowrank(V, state, chunk: int, eps: float):
    return _chunk_body(lambda j: V[:, j] @ V, state, chunk, eps)


# ---------------------------------------------------------------------------
# Dispatch-aware front doors
# ---------------------------------------------------------------------------


def greedy_init(spec, *, L=None, V=None, mask=None) -> GreedyState:
    """Initial resumable state for ``spec`` on a dense (L) or low-rank
    (V) kernel.  ``mask`` marks selectable candidates; it is folded into
    the state (masked entries can never be selected in any later chunk).
    """
    _check_kernel_args(spec, L, V)
    if spec.sharded():
        from repro.core.sharded import dpp_greedy_sharded_stream_init

        return dpp_greedy_sharded_stream_init(
            V, spec.k, mesh=spec.mesh, axis_name=spec.axis_name,
            window=spec.window, mask=mask, tile_m=spec.tile_m,
        )
    if spec.backend == "pallas":
        from repro.kernels.dpp_greedy import dpp_greedy_stream_init

        return dpp_greedy_stream_init(
            V, spec.k, mask=mask, window=spec.window, tile_m=spec.tile_m
        )
    return _init_jnp(spec.k, spec.window, L, V, mask)


def greedy_chunk(
    spec, state: GreedyState, *, L=None, V=None,
    chunk_size: Optional[int] = None,
):
    """Advance ``chunk_size`` greedy steps (default ``spec.chunk_size``).

    Returns ``(next_state, sel (chunk,), d_hist (chunk,))`` — with a
    leading batch axis on ``sel``/``d_hist`` for batched Pallas/sharded
    states.  Slots after an eps-stop hold -1 / 0, exactly as the
    whole-slate result's tail does.  The caller sizes chunks so the
    total never exceeds ``spec.k`` on the exact path (the windowed ring
    is unbounded); ``repro.core.dispatch.greedy_map_chunks`` does this.
    """
    _check_kernel_args(spec, L, V)
    chunk = resolve_chunk(spec, chunk_size)
    kern = L if L is not None else V
    record_chunk(
        _backend_label(spec),
        B=kern.shape[0] if kern.ndim == 3 else 1,
        chunk=chunk,
        M=kern.shape[-1],
    )
    if spec.sharded():
        from repro.core.sharded import dpp_greedy_sharded_stream_chunk

        return dpp_greedy_sharded_stream_chunk(
            V, state, chunk, mesh=spec.mesh, axis_name=spec.axis_name,
            eps=spec.eps, tile_m=spec.tile_m,
        )
    if spec.backend == "pallas":
        from repro.kernels.dpp_greedy import dpp_greedy_stream_chunk

        return dpp_greedy_stream_chunk(
            V, state, chunk, eps=spec.eps, tile_m=spec.tile_m,
        )
    fn = _chunk_dense if L is not None else _chunk_lowrank
    return fn(L if L is not None else V, state, chunk, float(spec.eps))


def greedy_step(spec, state: GreedyState, *, L=None, V=None):
    """One greedy step: ``(next_state, idx, d)`` with scalar ``idx``/``d``
    (-1 / 0 once eps-stopped).  Sugar for a chunk of one."""
    state, sel, dh = greedy_chunk(spec, state, L=L, V=V, chunk_size=1)
    return state, sel[..., 0], dh[..., 0]


# ---------------------------------------------------------------------------
# Session delta updates — recondition a windowed state on a pool delta
# ---------------------------------------------------------------------------
#
# A windowed state is fully determined by the pool ``V``, the last-w
# shown ids and the dead set (shown + masked): ``d2_i = L_ii -
# ||C[:, i]||^2`` for live i, and ``C[:, i] = V_W^{-1} L_{W, i}``
# depends only on the *window* columns of V.  So when a block of
# candidate columns is appended or overwritten, only that block's C
# columns and d2 entries change — everything else (the ring rows for
# shown items, every untouched column) is already correct.  The block
# is re-solved against the window factor directly: ``C[:, win]`` IS
# ``V_W`` (lower-triangular — column ``win[r]`` of C carries zeros
# above row r, see ``repro.core.windowed``), so one (w, w) gather plus
# one triangular solve reconditions dM columns in O(w^2 + w*dM*D) —
# never O(k * M) like a from-scratch rerun.


def _delta_cols(V, C, d2, win, start, V_blk, mask_blk, keep_dead: bool):
    """Recompute C/d2 for pool columns ``[start, start + dM)`` after
    writing ``V_blk`` there.  Unbatched leaves: V (D*, M*), C (w, M*),
    d2 (M*,), win (w,).  ``keep_dead`` preserves dead columns (d2 at
    -inf: shown, masked, padding) bit-for-bit — the rescore contract."""
    D, _ = V.shape
    w = C.shape[0]
    dtype = C.dtype
    dm = V_blk.shape[1]
    ids = jnp.clip(win, 0)
    valid = win >= 0

    # The window's lower-triangular Cholesky factor, read off C itself;
    # empty ring slots become identity rows so the solve is a no-op there.
    Vw = jnp.where(valid[:, None], C[:, ids].T, jnp.eye(w, dtype=dtype))
    # b[r] = L_{win[r], blk} from the (unchanged) window columns of V
    # full f32 products: a TPU's default f32 matmul rounds through bf16
    b = jnp.matmul(V[:, ids].T, V_blk, precision=jax.lax.Precision.HIGHEST)
    b = jnp.where(valid[:, None], b, 0.0)
    c = jax.scipy.linalg.solve_triangular(Vw, b, lower=True)  # (w, dm)
    diag_blk = jnp.sum(V_blk * V_blk, axis=0)
    d2_blk = jnp.where(mask_blk, diag_blk - jnp.sum(c * c, axis=0), NEG_INF)

    if keep_dead:
        oldV = jax.lax.dynamic_slice(V, (0, start), (D, dm))
        oldC = jax.lax.dynamic_slice(C, (0, start), (w, dm))
        oldd = jax.lax.dynamic_slice(d2, (start,), (dm,))
        dead = jnp.isneginf(oldd)
        V_blk = jnp.where(dead[None, :], oldV, V_blk)
        c = jnp.where(dead[None, :], oldC, c)
        d2_blk = jnp.where(dead, oldd, d2_blk)

    V = jax.lax.dynamic_update_slice(V, V_blk.astype(V.dtype), (0, start))
    C = jax.lax.dynamic_update_slice(C, c.astype(dtype), (0, start))
    d2 = jax.lax.dynamic_update_slice(d2, d2_blk.astype(d2.dtype), (start,))
    return V, C, d2


@partial(jax.jit, static_argnames=("keep_dead",))
def _delta_update(V, C, d2, win, start, V_blk, mask_blk, *, keep_dead: bool):
    return _delta_cols(V, C, d2, win, start, V_blk, mask_blk, keep_dead)


@partial(jax.jit, static_argnames=("keep_dead",))
def _delta_update_b1(V, C, d2, win, start, V_blk, mask_blk, *, keep_dead: bool):
    # batched single-lane leaves (the Pallas stream layout, B == 1)
    V, C1, d21 = _delta_cols(
        V, C[0], d2[0], win[0], start, V_blk, mask_blk, keep_dead
    )
    return V, C1[None], d21[None]


def _state_delta(spec, state, V, start, V_new, mask_new, keep_dead, op):
    if spec.sharded():
        raise NotImplementedError(
            f"{op} is not implemented for sharded states: the window ring "
            f"lives sharded behind shard_map and a column delta crosses "
            f"device boundaries.  Lands with the ROADMAP 'Router scale-up' "
            f"item (sharded slot batches + window heterogeneity); until "
            f"then re-rank sharded pools from scratch."
        )
    if state.win.shape[-1] == 0:
        raise ValueError(
            f"{op} needs a windowed state (cfg.window < slate_size): the "
            f"exact C (M, k) layout does not expose the conditioning "
            f"window, so a column delta cannot be re-solved in O(w*dM)"
        )
    if V_new.ndim != 2:
        raise ValueError(f"{op}: V_new must be (D, dM), got ndim={V_new.ndim}")
    dm = V_new.shape[1]
    M = V.shape[-1]
    if V_new.shape[0] > V.shape[0]:
        raise ValueError(
            f"{op}: V_new has D={V_new.shape[0]} rows but the pool operand "
            f"carries D={V.shape[0]}"
        )
    if isinstance(start, int):
        if start < 0 or start + dm > M:
            raise ValueError(
                f"{op}: block [{start}, {start + dm}) exceeds the pool's "
                f"{M} columns — size the session capacity up front"
            )
    if mask_new is None:
        mask_new = jnp.ones((dm,), bool)
    V_blk = V_new.astype(V.dtype)
    if V_blk.shape[0] < V.shape[0]:  # Pallas row padding (Dp >= D)
        V_blk = jnp.pad(V_blk, ((0, V.shape[0] - V_blk.shape[0]), (0, 0)))
    start = jnp.asarray(start, jnp.int32)
    if spec.backend == "pallas":
        if state.C.ndim != 3 or state.C.shape[0] != 1:
            raise ValueError(
                f"{op} takes a single-request Pallas stream state "
                f"(leading batch axis 1); slot-batched delta updates land "
                f"with the ROADMAP 'Router scale-up' item"
            )
        V2, C2, d22 = _delta_update_b1(
            V, state.C, state.d2, state.win, start, V_blk, mask_new,
            keep_dead=keep_dead,
        )
    else:
        V2, C2, d22 = _delta_update(
            V, state.C, state.d2, state.win, start, V_blk, mask_new,
            keep_dead=keep_dead,
        )
    # a delta can revive a stopped session: new/raised columns may now
    # clear the eps gate, so the latch re-arms and re-evaluates.  The
    # revived resume must condition on the *live* ring: a stopped chunk
    # advances t past the last real pick (its aborted steps revert
    # C/win but not the step counter), and a stale t >= w would evict a
    # window item that was never followed by a pick.  Ring occupancy is
    # the true pick count below w, and any t >= w is behaviorally
    # equivalent once the ring is full — so re-derive t from the ring.
    t2 = jnp.sum(state.win >= 0).astype(jnp.int32)
    new_state = GreedyState(
        t2, jnp.zeros_like(state.stopped), C2, d22, state.win
    )
    return new_state, V2


def greedy_state_extend(spec, state: GreedyState, V, start, V_new, mask_new=None):
    """Append ``dM`` candidate columns at ``start`` of the pool operand.

    Writes ``V_new (D, dM)`` into columns ``[start, start + dM)`` of
    ``V``, re-solves exactly those columns' Cholesky state against the
    session's current window and returns ``(state', V')`` — O(w * dM),
    independent of how many steps the state has already taken.  The
    target region is overwritten blind (it is the caller's padding /
    retired region); ``mask_new`` marks which of the new columns are
    selectable.  ``start`` may be a host int (bounds-checked) or traced;
    the block width ``dM`` is static — one compile per distinct width.
    Windowed states only; sharded raises ``NotImplementedError``.
    """
    return _state_delta(
        spec, state, V, start, V_new, mask_new, False, "greedy_state_extend"
    )


def greedy_state_rescore(spec, state: GreedyState, V, start, V_new, mask_new=None):
    """Overwrite ``dM`` *existing* columns with refreshed vectors.

    Same geometry and cost as :func:`greedy_state_extend`, with one
    contract change: dead columns (d2 at -inf — already shown, masked
    out, or padding) keep their exact old V/C/d2 bits, so the shown
    history and the window factor are never rewritten by a score
    refresh.  ``mask_new`` False additionally retires a live column.
    """
    return _state_delta(
        spec, state, V, start, V_new, mask_new, True, "greedy_state_rescore"
    )


# ---------------------------------------------------------------------------
# Slot-batched execution — the continuous-batching substrate
# ---------------------------------------------------------------------------
#
# The serving router (``repro.serving.router``) coalesces heterogeneous
# live requests into one padded micro-batch of S *slots* and advances
# all of them with a single chunk call per cycle.  Unlike the batched
# whole-slate paths — where every lane starts together — slots join and
# leave mid-flight (a freed slot is respliced with a brand-new request
# while its neighbours are deep into their slates), so the slot state
# carries a **per-slot step counter** ``t (S,)`` instead of the scalar
# the uniform batch paths share.  The per-step bodies already consume
# ``t`` per lane (it only feeds the Cholesky row index and the ring
# position), so the same op sequence runs; a slot's selections are
# bitwise those of a single-request state at the same ``t``.
#
# Layout: every leaf gains a leading slot axis — jnp exact
# ``C (S, M, k)``, windowed ``C (S, w, M)``, Pallas ``(S, R, Mp)``,
# sharded the global per-device views — and parked (empty) slots hold
# ``stopped=True`` with ``d2`` at -inf, so they select -1 at zero
# numerical risk while occupied neighbours compute.


def greedy_slot_state(spec, V, mask=None, dtype=None) -> GreedyState:
    """Single-request state in ``spec``'s slot layout.

    ``spec.k`` is the slot *capacity* (the router's ``max_slate``), not
    the request's own slate length — every slot shares one Cholesky
    geometry so states splice into any slot; a request simply stops
    consuming after its own ``k`` selections.  ``V (D, M)`` must already
    be padded to the router's bucket width (mask False over padding).
    ``dtype`` casts ``V`` first so the state's C/d2 leaves match the
    slot batch it will be spliced into (``state_splice`` casts leaf-wise
    — building the state in the wrong precision and upcasting later is
    NOT the same bits); the Pallas kernels compute in f32 regardless.
    """
    if dtype is not None:
        V = V.astype(dtype)
    if spec.sharded():
        from repro.core.sharded import dpp_greedy_sharded_stream_init

        return dpp_greedy_sharded_stream_init(
            V, spec.k, mesh=spec.mesh, axis_name=spec.axis_name,
            window=spec.window, mask=mask, tile_m=spec.tile_m,
        )
    if spec.backend == "pallas":
        from repro.kernels.dpp_greedy import dpp_greedy_stream_init

        st = dpp_greedy_stream_init(
            V, spec.k, mask=mask, window=spec.window, tile_m=spec.tile_m
        )
        # squeeze the kernels' (1, ...) batch leaves to the slot layout
        return GreedyState(st.t, st.stopped[0], st.C[0], st.d2[0], st.win[0])
    return _init_jnp(spec.k, spec.window, None, V, mask)


def slot_pad_v(spec, V, state):
    """Pad ``V`` to the slot executor's device geometry (Pallas (Dp, Mp)
    padding, sharded mesh/tile quantum; identity on jnp) so the per-cycle
    chunk calls move no O(D M) data."""
    if spec.sharded():
        from repro.core.sharded import _stream_pad

        return _stream_pad(V, state.d2.shape[-1])
    if spec.backend == "pallas":
        from repro.kernels.dpp_greedy import dpp_greedy_stream_pad

        return dpp_greedy_stream_pad(V, state)
    return V


def greedy_slots_init(spec, slots: int, D: int, M: int, dtype=jnp.float32):
    """Parked S-slot batch state + its zeroed V operand.

    Returns ``(state, V_slots)``: every slot is parked (``stopped``,
    ``d2`` -inf, ``t`` 0) and ``V_slots`` is zeros in the executor
    geometry — admit requests with :func:`state_splice`, free slots with
    :func:`state_evict`.  ``M`` is the router's padded bucket width and
    ``spec.k`` the per-slot capacity (see :func:`greedy_slot_state`).
    ``dtype`` is the resident V/C/d2 element type — it must match the
    lanes that will be spliced in, or ``state_splice``'s leaf-wise
    ``astype`` silently rounds every bf16/f64 request through it.
    """
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    Vz = jnp.zeros((D, M), dtype)
    single = greedy_slot_state(spec, Vz, mask=jnp.zeros((M,), bool))
    single = single._replace(stopped=jnp.asarray(True))
    state = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (slots,) + x.shape).copy(), single
    )
    Vp = slot_pad_v(spec, Vz, state)
    V_slots = jnp.zeros((slots,) + Vp.shape, Vp.dtype)
    return state, V_slots


def state_splice(state: GreedyState, single: GreedyState, slot) -> GreedyState:
    """Write a single-request state (``greedy_slot_state``, same spec and
    geometry) into ``slot`` of a slot-batched state.  ``slot`` may be a
    traced/int index — splicing never retriggers compilation."""
    i = jnp.asarray(slot, jnp.int32)
    return jax.tree_util.tree_map(
        lambda b, s: b.at[i].set(s.astype(b.dtype)), state, single
    )


def state_evict(state: GreedyState, slot) -> GreedyState:
    """Park ``slot``: eps-stopped with every candidate at -inf, step
    counter rewound — the slot selects -1 until a new request is
    spliced in.  The freed Cholesky rows are zeroed so a later splice
    starts from the same bits as a fresh single-request state."""
    i = jnp.asarray(slot, jnp.int32)
    win = state.win.at[i].set(-1) if state.win.shape[-1] else state.win
    return GreedyState(
        state.t.at[i].set(0),
        state.stopped.at[i].set(True),
        state.C.at[i].set(0.0),
        state.d2.at[i].set(NEG_INF),
        win,
    )


@partial(jax.jit, static_argnames=("chunk", "eps"))
def _chunk_lowrank_slots(V, state, chunk: int, eps: float):
    # one lane per slot; _chunk_body consumes the per-slot t scalar it
    # sees inside its lane, so heterogeneous progress just works
    return jax.vmap(
        lambda v, s: _chunk_body(lambda j: v[:, j] @ v, s, chunk, eps)
    )(V, state)


def greedy_chunk_slots(spec, state: GreedyState, V_slots, chunk: int):
    """Advance every slot ``chunk`` greedy steps in one batched call.

    ``V_slots (S, D*, M*)`` is the stacked per-slot kernel operand in
    executor geometry (``greedy_slots_init`` / ``slot_pad_v``).  Returns
    ``(state, sel (S, chunk), d_hist (S, chunk))`` — parked and stopped
    slots yield -1 / 0.  One jit cache entry per (geometry, chunk): the
    per-request k / mask / progress all live in data, never in statics.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    record_chunk(
        _backend_label(spec),
        B=V_slots.shape[0],
        chunk=chunk,
        M=V_slots.shape[-1],
    )
    if spec.sharded():
        from repro.core.sharded import dpp_greedy_sharded_stream_chunk

        return dpp_greedy_sharded_stream_chunk(
            V_slots, state, chunk, mesh=spec.mesh, axis_name=spec.axis_name,
            eps=spec.eps, tile_m=spec.tile_m,
        )
    if spec.backend == "pallas":
        from repro.kernels.dpp_greedy import dpp_greedy_stream_chunk

        return dpp_greedy_stream_chunk(
            V_slots, state, chunk, eps=spec.eps, tile_m=spec.tile_m,
        )
    return _chunk_lowrank_slots(V_slots, state, chunk, float(spec.eps))
