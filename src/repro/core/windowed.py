"""Windowed Div-DPP (beyond-paper; the NeurIPS'18 version of this work
adds a sliding-window variant for long result sequences).

Diversity is enforced only against the last ``w`` selected items: the
DPP kernel is restricted to the window, so slate length is unbounded
with O(w * M) state.

Two implementations live here:

* ``dpp_greedy_windowed`` / ``dpp_greedy_windowed_lowrank`` — the
  paper's **incremental** update, O(w M) per step.  State is the window
  Cholesky factor's action on every candidate, ``C (w, M)`` with
  ``C[:, i] = V_W^{-1} L_{W, i}`` kept in window order (row 0 =
  oldest pick).  Appending a pick is the paper's eq. 16-18 row append
  (one (w,)x(w, M) matvec); evicting the oldest pick is a first-row
  Cholesky *downdate*: ``w - 1`` Givens rotations applied to the rows
  of ``C``.  Because ``C[:, win]`` *is* ``V_W^T``, the rotations are
  computed from ``C`` itself — no separate factor is stored, and
  ``d_i^2`` is repaired in O(M) from the rotation residue
  (``d2 += u_fin^2``) instead of recomputed.

* ``dpp_greedy_windowed_rebuild`` — the original O(w^2 M)-per-step
  reference: per step the window's Cholesky factor is rebuilt (O(w^3))
  and every candidate is re-solved against it (a batched triangular
  solve).  Slower by a factor w but independently derived — kept as
  the correctness oracle for the incremental path and the Pallas
  windowed kernel.

Why the downdate is just rotations on rows of ``C``:  drop the oldest
window item and split the factor ``V = [[v00, 0], [v, V22]]``.  The
shrunken Gram is ``V22 V22^T + v v^T``, so the new factor is the
rank-1 Cholesky *update* of ``V22`` by ``v`` — a product of Givens
rotations ``Q`` with ``[V22 | v] Q = [V' | 0]``.  The same ``Q^T``
applied to the stacked rows ``[C_1; c_0]`` (surviving rows over the
evicted row) yields the new ``C`` rows exactly, and the evicted
residue row ``u_fin`` carries the norm lost per column
(``||C'||^2 = ||C||^2 - u_fin^2``), which is the ``d2`` repair.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.greedy_chol import NEG_INF, GreedyResult


def greedy_step_windowed(row_fn, t, C, d2, win, stopped, *, w, eps2, tiny):
    """One sliding-window greedy step on the ring state ``C (w, M)``.

    Factored out of the ``_windowed_loop`` fori body so the whole-slate
    loop and the chunked/resumable executors in ``repro.core.streaming``
    run the *identical* op sequence — streamed chunks concatenate
    bitwise to the whole-slate result.  ``t`` is the absolute step
    index (it decides eviction, ``t >= w``, and the ring row ``pos``).

    Returns ``(C, d2, win, stopped, j, dj)``.
    """
    M = d2.shape[0]
    dtype = d2.dtype
    C0, d20, win0 = C, d2, win

    # ---- select against the current window of min(t, w) picks
    # (paper eq. 13; d2 is maintained incrementally across steps)
    j = jnp.argmax(d2)
    dj2 = d2[j]
    stopped = stopped | (dj2 <= eps2)
    dj = jnp.sqrt(jnp.maximum(dj2, eps2))

    # ---- evict the oldest window item to make room (window full only)
    full = jnp.logical_and(t >= w, jnp.logical_not(stopped))
    u = jnp.where(full, C[0], jnp.zeros((M,), dtype))
    win_shift = jnp.roll(win, -1)  # win_shift[r] = old win[r+1]

    def rot(r, Cu):
        C, u = Cu
        # when not evicting, read row r and rotate by identity (no-op)
        read = jnp.where(full, r + 1, r)
        row = jax.lax.dynamic_slice(C, (read, 0), (1, M))[0]
        idx = jnp.clip(win_shift[r], 0)
        a = row[idx]  # current window-factor diagonal V22[r, r]
        b = u[idx]  # current downdate vector entry v[r]
        rho = jnp.maximum(jnp.sqrt(a * a + b * b), tiny)
        cos = jnp.where(full, a / rho, 1.0)
        sin = jnp.where(full, b / rho, 0.0)
        new_row = cos * row + sin * u
        u = cos * u - sin * row
        C = jax.lax.dynamic_update_slice(C, new_row[None], (r, 0))
        return C, u

    C, u = jax.lax.fori_loop(0, w - 1, rot, (C, u))
    # the evicted slot: stale last row is cleared, d2 regains the
    # norm carried away by the rotation residue row
    C = jnp.where(full, C.at[w - 1].set(0.0), C)
    d2 = jnp.where(full, d2 + u * u, d2)
    win = jnp.where(full, win_shift.at[w - 1].set(-1), win)

    # ---- append j against the *post-eviction* window (eqs. 16-18);
    # its marginal there is d2[j] repaired by the eviction (>= dj2)
    djp = jnp.sqrt(jnp.maximum(d2[j], eps2))
    e = (row_fn(j) - C[:, j] @ C) / djp
    pos = jnp.minimum(t, w - 1)
    C_next = jax.lax.dynamic_update_slice(C, e[None], (pos, 0))
    d2_next = (d2 - e * e).at[j].set(NEG_INF)
    win_next = win.at[pos].set(j)

    C = jnp.where(stopped, C0, C_next)
    d2 = jnp.where(stopped, d20, d2_next)
    win = jnp.where(stopped, win0, win_next)
    return C, d2, win, stopped, j, dj


def _windowed_loop(
    diag: jnp.ndarray,
    row_fn: Callable[[jnp.ndarray], jnp.ndarray],
    k: int,
    window: int,
    eps: float,
    mask: jnp.ndarray,
) -> GreedyResult:
    """Incremental sliding-window greedy, O(w M) per step.

    diag:   (M,) float — L_ii for every candidate.
    row_fn: j -> (M,) float — returns row L_j of the kernel.
    """
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    M = diag.shape[0]
    w = min(window, k)
    dtype = diag.dtype
    eps2 = jnp.asarray(eps, dtype) ** 2
    tiny = jnp.asarray(1e-30, dtype)

    d2 = jnp.where(mask, diag, NEG_INF)
    C = jnp.zeros((w, M), dtype)
    win = jnp.full((w,), -1, jnp.int32)  # window order: 0 = oldest
    sel = jnp.full((k,), -1, jnp.int32)
    d_hist = jnp.zeros((k,), dtype)

    def body(t, state):
        C, d2, win, sel, d_hist, stopped = state
        C, d2, win, stopped, j, dj = greedy_step_windowed(
            row_fn, t, C, d2, win, stopped, w=w, eps2=eps2, tiny=tiny
        )
        sel = sel.at[t].set(jnp.where(stopped, -1, j))
        d_hist = d_hist.at[t].set(jnp.where(stopped, 0.0, dj))
        return C, d2, win, sel, d_hist, stopped

    state = (C, d2, win, sel, d_hist, jnp.asarray(False))
    _, _, _, sel, d_hist, _ = jax.lax.fori_loop(0, k, body, state)
    return GreedyResult(sel, jnp.sum(sel >= 0).astype(jnp.int32), d_hist)


@partial(jax.jit, static_argnames=("k", "window", "eps"))
def dpp_greedy_windowed(
    L: jnp.ndarray,
    k: int,
    window: int = 10,
    eps: float = 1e-6,
    mask: Optional[jnp.ndarray] = None,
) -> GreedyResult:
    """Greedy MAP with a sliding diversity window of the last ``w`` picks.

    L (M, M) dense kernel.  With ``window >= k`` this equals the exact
    Algorithm 1 (tested); smaller windows trade global diversity for
    unbounded slate length at O(w M) per step.
    """
    if mask is None:
        mask = jnp.ones((L.shape[0],), bool)
    return _windowed_loop(jnp.diagonal(L), lambda j: L[j], k, window, eps, mask)


@partial(jax.jit, static_argnames=("k", "window", "eps"))
def dpp_greedy_windowed_lowrank(
    V: jnp.ndarray,
    k: int,
    window: int = 10,
    eps: float = 1e-6,
    mask: Optional[jnp.ndarray] = None,
) -> GreedyResult:
    """Sliding-window greedy on the implicit kernel ``L = V^T V``, V (D, M).

    Never materializes M^2 memory; row ``L_j = V[:, j] @ V`` is
    recomputed per step exactly as in ``dpp_greedy_lowrank``.
    """
    if mask is None:
        mask = jnp.ones((V.shape[1],), bool)
    diag = jnp.sum(V * V, axis=0)
    return _windowed_loop(diag, lambda j: V[:, j] @ V, k, window, eps, mask)


@partial(jax.jit, static_argnames=("k", "window", "eps"))
def dpp_greedy_windowed_batch(
    L: jnp.ndarray,
    k: int,
    window: int = 10,
    eps: float = 1e-6,
    mask: Optional[jnp.ndarray] = None,
) -> GreedyResult:
    """vmap over users: L (B, M, M), mask (B, M)."""
    if mask is None:
        mask = jnp.ones(L.shape[:2], bool)
    fn = lambda Li, mi: _windowed_loop(
        jnp.diagonal(Li), lambda j: Li[j], k, window, eps, mi
    )
    return jax.vmap(fn)(L, mask)


@partial(jax.jit, static_argnames=("k", "window", "eps"))
def dpp_greedy_windowed_lowrank_batch(
    V: jnp.ndarray,
    k: int,
    window: int = 10,
    eps: float = 1e-6,
    mask: Optional[jnp.ndarray] = None,
) -> GreedyResult:
    """vmap over users: V (B, D, M), mask (B, M)."""
    if mask is None:
        mask = jnp.ones((V.shape[0], V.shape[2]), bool)
    fn = lambda Vi, mi: _windowed_loop(
        jnp.sum(Vi * Vi, axis=0), lambda j: Vi[:, j] @ Vi, k, window, eps, mi
    )
    return jax.vmap(fn)(V, mask)


@jax.jit
def windowed_state_rebuild(V, shown, dead):
    """Rebuild the incremental ring state ``(C, d2)`` from history alone.

    A windowed state is a pure function of the pool ``V (D, M)``, the
    last ``w`` shown pool columns (``shown (w,)`` int32, oldest first,
    -1-padded at the tail) and the dead set (``dead (M,)`` bool — every
    ever-shown or masked-out column, padding included).  The window's
    Gram is PD without jitter (every pick cleared the eps gate, so the
    incremental factor's diagonal is >= eps), and the Cholesky factor
    is unique — so this rebuild lands on the same ``C (w, M)`` rows the
    incremental path reached, up to rounding (~1 ulp).

    This is the session layer's eviction-repair: a session dropped from
    the LRU byte budget is rebuilt bit-compatibly from its host-side
    history the next time it is touched (``repro.serving.session``).
    """
    dtype = V.dtype
    w = shown.shape[0]
    ids = jnp.clip(shown, 0)
    valid = shown >= 0
    Vwin = jnp.where(valid[:, None], V[:, ids].T, 0.0)  # (w, D) rows
    eye = jnp.eye(w, dtype=dtype)
    vm = valid[:, None] & valid[None, :]
    # full f32 products: a TPU's default f32 matmul rounds through bf16
    hi = jax.lax.Precision.HIGHEST
    Lw = jnp.where(vm, jnp.matmul(Vwin, Vwin.T, precision=hi), eye)
    F = jnp.linalg.cholesky(Lw)
    Lwi = jnp.matmul(Vwin, V, precision=hi)  # (w, M); zero rows at empty slots
    C = jax.scipy.linalg.solve_triangular(F, Lwi, lower=True)
    C = jnp.where(valid[:, None], C, 0.0)
    d2 = jnp.sum(V * V, axis=0) - jnp.sum(C * C, axis=0)
    d2 = jnp.where(dead, NEG_INF, d2)
    return C, d2


@partial(jax.jit, static_argnames=("k", "window", "eps"))
def dpp_greedy_windowed_rebuild(
    L: jnp.ndarray,
    k: int,
    window: int = 10,
    eps: float = 1e-6,
    mask: Optional[jnp.ndarray] = None,
) -> GreedyResult:
    """Reference sliding-window greedy: rebuild + re-solve every step.

    O(w^2 M) per step (vs the incremental path's O(w M)); independently
    derived, kept as the oracle the fast paths are tested against.
    """
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    M = L.shape[0]
    w = min(window, k)
    dtype = L.dtype
    eps2 = jnp.asarray(eps, dtype) ** 2
    if mask is None:
        mask = jnp.ones((M,), bool)

    diag = jnp.diagonal(L)
    sel = jnp.full((k,), -1, jnp.int32)
    d_hist = jnp.zeros((k,), dtype)
    # ring buffer of the last w selected ids (-1 = empty)
    win = jnp.full((w,), -1, jnp.int32)
    avail = jnp.where(mask, 0.0, NEG_INF).astype(dtype)

    def body(t, state):
        sel, d_hist, win, avail, stopped = state
        # Build the window's kernel and Cholesky factor.  Empty slots use
        # an identity row/col so the factor stays well-defined.
        ids = jnp.clip(win, 0)
        valid = win >= 0
        Lw = L[jnp.ix_(ids, ids)]
        eye = jnp.eye(w, dtype=dtype)
        vm = valid[:, None] & valid[None, :]
        Lw = jnp.where(vm, Lw, eye)
        V = jnp.linalg.cholesky(Lw + 1e-6 * eye)

        # c_i = V^{-1} L_{W,i} for all candidates (batched triangular solve)
        Lwi = jnp.where(valid[:, None], L[ids], 0.0)  # (w, M)
        C = jax.scipy.linalg.solve_triangular(V, Lwi, lower=True)  # (w, M)
        d2 = diag - jnp.sum(C * C, axis=0)
        d2 = d2 + avail  # -inf for taken/masked

        j = jnp.argmax(d2)
        dj2 = d2[j]
        stopped = stopped | (dj2 <= eps2)
        dj = jnp.sqrt(jnp.maximum(dj2, eps2))

        sel = sel.at[t].set(jnp.where(stopped, -1, j))
        d_hist = d_hist.at[t].set(jnp.where(stopped, 0.0, dj))
        win = jnp.where(stopped, win, win.at[t % w].set(j))
        avail = jnp.where(stopped, avail, avail.at[j].set(NEG_INF))
        return sel, d_hist, win, avail, stopped

    sel, d_hist, _, _, _ = jax.lax.fori_loop(
        0, k, body, (sel, d_hist, win, avail, jnp.asarray(False))
    )
    return GreedyResult(sel, jnp.sum(sel >= 0).astype(jnp.int32), d_hist)
