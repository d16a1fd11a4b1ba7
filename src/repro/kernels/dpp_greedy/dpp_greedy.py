"""Pallas TPU kernels: whole-slate greedy DPP MAP inference, VMEM-resident.

TPU-native adaptation of the paper's Algorithm 1 (DESIGN.md §3):

* the kernel never materializes ``L`` — it holds the *scaled feature*
  matrix ``V (D, M)`` (``L = V^T V``) in VMEM and recomputes the needed
  kernel row ``L_j = V[:, j]^T V`` each step (an f32 multiply-reduce);
* the Cholesky-state matrix ``C`` is laid out **(N, M)** — step ``t``
  writes *row* ``t`` instead of the paper's per-candidate column
  append, and the update inner product ``<c_j, c_i>`` for all ``i`` is
  the matvec ``c_j^T C``;
* the entire N-step greedy loop runs inside one kernel invocation with
  zero HBM round-trips between steps; the grid dimension is the *user
  batch* (one program = one user's slate).

``window=w`` switches to the **sliding-window** kernel (the NeurIPS'18
long-sequence variant): ``C`` shrinks to a ``(w, M)`` ring of window
Cholesky rows, so the slate length ``N`` is unbounded while VMEM stays
O(w M).  Each step is select (argmax over the maintained ``d2``), evict
(the first-row Cholesky downdate — ``w - 1`` Givens rotations swept over
the rows of ``C``, with the rotation residue row repairing ``d2``), and
append (the same eq. 16-18 row append as the full kernel, against the
post-eviction window).  See ``repro.core.windowed`` for the math.

Both kernels call the per-tile update functions of ``tiled.py`` over the
whole M (one tile), so resident, tiled and fused chunk kernels compute
the same bits; the winner's columns are read by one-hot reduction
(Mosaic has no lane-axis dynamic slice).

VMEM working set (resident mode): ``V`` (D*M*4) + ``C`` (N*M*4, or
w*M*4 windowed) + ``d2/e`` rows — e.g. D=128, M=4096, N=64: 2 MB +
1 MB (``tiling.untiled_vmem_bytes``), against a 12 MB budget and a
64 MB scoped-VMEM compile limit.  These kernels hold that working set
*whole*, which is what buys the zero-HBM-round-trip greedy loop — and
what caps M.  Past the budget the ops.py wrapper dispatches the tiled
streaming kernels in ``tiled.py`` instead (per-step grid sweeps over
``(D, tile_m)`` blocks, double-buffered HBM<->VMEM, VMEM bounded per
*tile* by ``tiling.tile_vmem_bytes``) — there is no silent jnp fallback
at scale any more; the jnp oracle needs an explicit ``force_jnp=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dpp_greedy.tiled import (
    _argmax_first,
    _evict_coeffs_tile,
    _lane_pick,
    _lane_set,
    _row_set,
    _tile_update_full,
    _tile_update_windowed,
    COMPILER_PARAMS,
)
from repro.kernels.platform import resolve_interpret

NEG_INF = float("-inf")


def _init(v_ref, mask_ref, sel_ref, dhist_ref, c_ref, d2_ref):
    V = v_ref[...]
    diag = jnp.sum(V * V, axis=0, keepdims=True)  # (1, M)
    d2_ref[...] = jnp.where(mask_ref[...] > 0, diag, NEG_INF)
    c_ref[...] = jnp.zeros(c_ref.shape, jnp.float32)
    sel_ref[...] = jnp.full(sel_ref.shape, -1, jnp.int32)
    dhist_ref[...] = jnp.zeros(dhist_ref.shape, jnp.float32)


def _kernel(v_ref, mask_ref, sel_ref, dhist_ref, c_ref, d2_ref, *, k: int,
            eps: float):
    """One user's full greedy slate.

    v_ref:    (D, M) f32 — scaled features, L = V^T V
    mask_ref: (1, M) f32 — 1.0 where selectable
    sel_ref:  (1, N) i32 out
    dhist_ref:(1, N) f32 out
    c_ref:    (N, M) f32 VMEM scratch — incremental Cholesky rows
    d2_ref:   (1, M) f32 VMEM scratch — marginal gains
    """
    eps2 = eps * eps
    _init(v_ref, mask_ref, sel_ref, dhist_ref, c_ref, d2_ref)
    M = v_ref.shape[1]

    def body(t, stopped):  # stopped: (1, 1) int32 (no i1 loop carries)
        V, C, d2 = v_ref[...], c_ref[...], d2_ref[...]
        dj2, j = _argmax_first(d2)
        stopped = jnp.logical_or(stopped > 0, dj2 <= eps2)
        dj = jnp.sqrt(jnp.maximum(dj2, eps2))
        # the winner's columns by one-hot lane reduction (Mosaic has no
        # lane-axis dynamic slice), then the shared per-tile update
        e, d2o = _tile_update_full(
            V, C, d2, _lane_pick(V, j), _lane_pick(C, j), dj, stopped, j,
            0, 0, M,
        )
        c_ref[...] = _row_set(C, t, e)
        d2_ref[...] = d2o
        sel_ref[...] = _lane_set(sel_ref[...], t, jnp.where(stopped, -1, j))
        dhist_ref[...] = _lane_set(
            dhist_ref[...], t, jnp.where(stopped, 0.0, dj)
        )
        return stopped.astype(jnp.int32)

    jax.lax.fori_loop(0, k, body, jnp.zeros((1, 1), jnp.int32))


def _kernel_windowed(
    v_ref, mask_ref, sel_ref, dhist_ref, c_ref, d2_ref, *, k: int, w: int,
    eps: float,
):
    """One user's full slate with a sliding diversity window of ``w``.

    v_ref:    (D, M) f32 — scaled features, L = V^T V
    mask_ref: (1, M) f32 — 1.0 where selectable
    sel_ref:  (1, N) i32 out (N = k, unbounded)
    dhist_ref:(1, N) f32 out
    c_ref:    (w, M) f32 VMEM scratch — ring of window Cholesky rows in
              window order (row 0 = oldest pick still in the window)
    d2_ref:   (1, M) f32 VMEM scratch — marginal gains

    Each step gathers the (w, w) window factor ``C[:, win]``, derives
    the eviction rotations from it (:func:`_evict_coeffs_tile`) and
    applies evict + append in one pass — the same per-tile update the
    tiled and fused chunk kernels run, over the whole M.
    """
    eps2 = eps * eps
    _init(v_ref, mask_ref, sel_ref, dhist_ref, c_ref, d2_ref)
    M = v_ref.shape[1]

    def body(t, carry):
        win, stopped = carry
        V, C, d2 = v_ref[...], c_ref[...], d2_ref[...]
        dj2, j = _argmax_first(d2)
        stopped = jnp.logical_or(stopped > 0, dj2 <= eps2)
        dj = jnp.sqrt(jnp.maximum(dj2, eps2))
        full = jnp.logical_and(t >= w, jnp.logical_not(stopped))

        Cw = jnp.zeros((w, w), jnp.float32)
        for r in range(w):
            idx = _lane_pick(win, r)
            col = jnp.where(idx >= 0, _lane_pick(C, idx), 0.0)
            Cw = _lane_set(Cw, r, col)
        coss, sins, cj_post, d2j = _evict_coeffs_tile(
            Cw, _lane_pick(C, j), dj2, full, w
        )
        djp = jnp.sqrt(jnp.maximum(d2j, eps2))
        pos = jnp.minimum(t, w - 1)
        C_out, d2o, _ = _tile_update_windowed(
            V, C, d2, _lane_pick(V, j), cj_post, djp, stopped, full,
            coss, sins, j, 0, pos, 0, w, M,
        )
        c_ref[...] = C_out
        d2_ref[...] = d2o

        shifted = jnp.full((1, w), -1, jnp.int32)
        for c in range(w - 1):
            shifted = _lane_set(shifted, c, _lane_pick(win, c + 1))
        win1 = jnp.where(full, shifted, win)
        win = jnp.where(stopped, win, _lane_set(win1, pos, j))

        sel_ref[...] = _lane_set(sel_ref[...], t, jnp.where(stopped, -1, j))
        dhist_ref[...] = _lane_set(
            dhist_ref[...], t, jnp.where(stopped, 0.0, dj)
        )
        return win, stopped.astype(jnp.int32)

    win0 = jnp.full((1, w), -1, jnp.int32)
    jax.lax.fori_loop(0, k, body, (win0, jnp.zeros((1, 1), jnp.int32)))


@functools.partial(jax.jit, static_argnames=("k", "window", "eps", "interpret"))
def dpp_greedy_kernel(
    V: jnp.ndarray,
    mask: jnp.ndarray,
    k: int,
    window: int | None = None,
    eps: float = 1e-3,
    interpret=None,
):
    """Batched greedy DPP MAP on TPU.

    V:    (B, D, M) f32 scaled features (columns = alpha^r_i * f_i)
    mask: (B, M) bool/float — selectable candidates
    window: sliding diversity window ``w`` (None = full, exact Alg. 1);
        with ``w < k`` the VMEM state is O(w M) so ``k`` is unbounded.
    Returns (sel (B, k) i32, d_hist (B, k) f32).
    """
    B, D, M = V.shape
    mask = mask.astype(jnp.float32).reshape(B, 1, M)

    if window is not None and window < k:
        kernel = functools.partial(_kernel_windowed, k=k, w=window, eps=eps)
        state_rows, name = window, "dpp_resident_windowed"
    else:
        kernel = functools.partial(_kernel, k=k, eps=eps)
        state_rows, name = k, "dpp_resident_exact"
    sel, dhist = pl.pallas_call(
        kernel,
        name=name,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, D, M), lambda b: (b, 0, 0)),
            pl.BlockSpec((None, 1, M), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k), lambda b: (b, 0, 0)),
            pl.BlockSpec((None, 1, k), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((state_rows, M), jnp.float32),
            pltpu.VMEM((1, M), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(V.astype(jnp.float32), mask)
    return sel[:, 0, :], dhist[:, 0, :]
