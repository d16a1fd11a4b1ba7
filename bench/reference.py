"""The plain reference: greedy MAP of a DPP in float64 NumPy.

Independent of the program under test (it imports nothing of
``repro``).  ``shortlist``, ``kernel_columns``, ``ref_greedy`` and
``ref_rerank`` are copies of the float64 reference in ``chip_smoke.py``,
with ``alpha`` and ``eps`` as arguments instead of module constants:
Algorithm 1 of Chen et al. (2018), or its sliding-window form, with a
fresh Cholesky of the shown columns' Gram matrix at every step, so no
incremental state is carried.

``follow`` replays a served slate through the same reference: at every
step it conditions on the slate's own earlier picks and reads how far
the pick's gain lies below the best gain on offer, and how far the
served gain lies from the reference's.  That is how a slate is judged
(``bench.check``): a greedy slate is a sequence of argmax decisions,
and where two candidates' gains differ by less than float32 rounding
either pick is the greedy's.

``control_greedy`` is the reference computed one precision step below
the configurations' float32: every product of ``V`` takes bfloat16
operands with float32 accumulation (what a TPU does with a float32
matrix product at its default precision), the rest is float32.  It
stands in the program's place to show that the comparison catches that
step.
"""
from __future__ import annotations

import numpy as np
from scipy.linalg import solve_triangular


def shortlist(scores, C, mask=None):
    """Global ids of the top-C selectable scores."""
    s = scores.astype(np.float64)
    if mask is not None:
        s = np.where(mask, s, -np.inf)
    return np.argsort(-s, kind="stable")[:C]


def kernel_columns(feats, scores, alpha):
    """The low-rank DPP kernel's columns, L = V^T V: V = (rel * f)^T."""
    rel = alpha ** scores.astype(np.float64)
    return (feats.astype(np.float64) * rel[:, None]).T


def ref_greedy(V, k, eps, window=None, shown=(), dead=None):
    """Greedy MAP over the columns of ``V (D, n)`` in float64.

    Each step conditions every column on the last ``window`` shown
    columns (all of them when ``window`` is None) by a fresh Cholesky
    of their Gram matrix.  Returns ``(columns, gains)``; stops early
    where the best gain is <= ``eps``.
    """
    diag = np.einsum("dm,dm->m", V, V)
    shown = list(shown)
    dead = np.zeros(V.shape[1], bool) if dead is None else dead.copy()
    dead[shown] = True
    picks, gains = [], []
    for _ in range(k):
        win = shown if window is None else shown[-window:]
        d2 = diag.copy()
        if win:
            Vw = V[:, win]
            F = np.linalg.cholesky(Vw.T @ Vw)
            Ci = np.linalg.solve(F, Vw.T @ V)
            d2 -= np.einsum("wm,wm->m", Ci, Ci)
        d2[dead] = -np.inf
        j = int(np.argmax(d2))
        if not d2[j] > eps * eps:
            break
        picks.append(j)
        gains.append(np.sqrt(d2[j]))
        shown.append(j)
        dead[j] = True
    return np.asarray(picks, np.int64), np.asarray(gains)


def ref_rerank(feats, scores, C, k, alpha, eps, window=None, mask=None):
    """The reference slate in global ids, -1 past an eps-stop."""
    ids = shortlist(scores, C, mask)
    cols, gains = ref_greedy(kernel_columns(feats[ids], scores[ids], alpha),
                             k, eps, window)
    slate = np.full(k, -1, np.int64)
    slate[: cols.size] = ids[cols]
    return slate, gains


class Followed:
    """What ``follow`` read off one served slate.

    ``pick_gap``: the largest relative shortfall, over the slate's
    steps, of the pick's reference gain below the best reference gain
    on offer (stopping counts as a pick of gain ``eps``).
    ``gain_err``: the largest relative error of a served gain.
    ``invalid``: a description of the first structural fault (an id
    outside the shortlist or masked, a repeated id, a -1 before a
    later pick), or None.
    """

    __slots__ = ("pick_gap", "gain_err", "invalid", "steps")

    def __init__(self, pick_gap=0.0, gain_err=0.0, invalid=None, steps=0):
        self.pick_gap = pick_gap
        self.gain_err = gain_err
        self.invalid = invalid
        self.steps = steps


def follow(feats, scores, C, ids, gains, alpha, eps, window=None,
           mask=None, columns=None):
    """Replay one served slate ``(ids, gains)`` (global ids, -1 past a
    stop) through the float64 reference, conditioned on its own picks.

    ``columns`` may pass ``(shortlist ids, kernel_columns)`` already
    built for this request.  Returns a :class:`Followed`.
    """
    ids = np.asarray(ids).reshape(-1).astype(np.int64)
    gains = np.asarray(gains).reshape(-1).astype(np.float64)
    k = ids.size
    if columns is None:
        sl = shortlist(scores, C, mask)
        V = kernel_columns(feats[sl], scores[sl], alpha)
    else:
        sl, V = columns
    n = int(np.sum(ids >= 0))
    if np.any(ids[n:] >= 0) or np.any(ids[:n] < 0):
        return Followed(invalid="a -1 before a later pick")
    inv = np.full(int(max(sl.max(), ids.max(initial=0))) + 1, -1, np.int64)
    inv[sl] = np.arange(sl.size)
    p = inv[ids[:n]]
    if np.any(p < 0):
        return Followed(invalid=f"id {int(ids[:n][p < 0][0])} is not in "
                                f"the shortlist")
    if np.unique(p).size != n:
        return Followed(invalid="a repeated id")
    dead = np.zeros(sl.size, bool)
    if mask is not None:
        dead |= ~np.asarray(mask)[sl]
        if np.any(dead[p]):
            return Followed(invalid="a masked id")
    diag = np.einsum("dm,dm->m", V, V)
    G = V[:, p].T @ V  # (n, C): the picks' rows of L
    exact = window is None or window >= k
    if exact and n:
        try:
            L = np.linalg.cholesky(G[:, p])
        except np.linalg.LinAlgError:
            return Followed(invalid="the picks' Gram matrix is singular")
        cum = np.cumsum(solve_triangular(L, G, lower=True) ** 2, axis=0)
    pick_gap = gain_err = 0.0
    for t in range(min(n + 1, k)):
        if t == 0:
            d2 = diag.copy()
        elif exact:
            d2 = diag - cum[t - 1]
        else:
            w = p[max(0, t - window):t]
            try:
                F = np.linalg.cholesky(G[t - w.size:t][:, w])
            except np.linalg.LinAlgError:
                return Followed(invalid="a window's Gram matrix is singular")
            Ci = solve_triangular(F, G[t - w.size:t], lower=True)
            d2 = diag - np.einsum("wm,wm->m", Ci, Ci)
        d2[dead] = -np.inf
        best = max(np.sqrt(max(float(d2.max()), 0.0)), eps)
        if t < n:
            got = np.sqrt(max(float(d2[p[t]]), 0.0))
            gain_err = max(gain_err, abs(gains[t] - got) / got if got > 0
                           else 1.0)
        else:  # the slate stopped here: a pick of gain eps
            got = eps
        pick_gap = max(pick_gap, (best - got) / best)
        if t < n:
            dead[p[t]] = True
    return Followed(pick_gap, gain_err, None, n)


# ---------------------------------------------------------------------------
# The control: the reference one precision step below float32
# ---------------------------------------------------------------------------


def _bf16(x):
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def control_greedy(V, k, eps, window=None, dead=None):
    """``ref_greedy`` with every product of ``V`` taken as a TPU takes a
    float32 matrix product at its default precision: bfloat16 operands,
    float32 accumulation; the rest in float32.  Returns
    ``(columns, gains)``."""
    Vb = _bf16(V)
    diag = np.einsum("dm,dm->m", Vb, Vb)
    dead = np.zeros(V.shape[1], bool) if dead is None else dead.copy()
    shown, picks, gains = [], [], []
    for _ in range(k):
        win = shown if window is None else shown[-window:]
        d2 = diag.copy()
        if win:
            Wt = Vb[:, win].T
            F = np.linalg.cholesky(Wt @ Vb[:, win])
            Ci = solve_triangular(F, Wt @ Vb, lower=True)
            d2 = d2 - np.einsum("wm,wm->m", Ci, Ci)
        d2[dead] = -np.inf
        j = int(np.argmax(d2))
        if not d2[j] > eps * eps:
            break
        picks.append(j)
        gains.append(float(np.sqrt(d2[j])))
        shown.append(j)
        dead[j] = True
    return np.asarray(picks, np.int64), np.asarray(gains)


def control_rerank(feats, scores, C, k, alpha, eps, window=None, mask=None):
    """The control's slate in global ids (-1 past a stop) and gains."""
    ids = shortlist(scores, C, mask)
    rel = np.exp(scores[ids].astype(np.float32) * np.float32(np.log(alpha)))
    V = (np.asarray(feats[ids], np.float32) * rel[:, None]).T
    dead = None if mask is None else ~np.asarray(mask)[ids]
    cols, gains = control_greedy(V, k, eps, window, dead)
    slate = np.full(k, -1, np.int64)
    slate[: cols.size] = ids[cols]
    g = np.zeros(k)
    g[: gains.size] = gains
    return slate, g
