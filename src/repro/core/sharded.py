"""Sharded candidate-axis greedy MAP — one slate over millions of candidates.

The paper's Algorithm 1 costs O(D M) per step on the low-rank kernel
``L = V^T V``; the per-step work (a candidate matvec plus an argmax) is
embarrassingly parallel over the candidate axis M, exactly the structure
Han et al. (arXiv:1703.03389) exploit for parallel greedy DPP inference.
Because each candidate only needs its own column of ``V`` (Gartrell et
al., arXiv:1602.05436 low-rank factorization), device ``p`` of a
P-device mesh computes on just the ``(D, M/P)`` column shard plus its
slice of the ``c``/``d2`` Cholesky state — the dense ``(M, M)`` kernel
``L`` never exists anywhere.  (The serving front end still builds the
full ``(D, M)`` ``V`` from the features before laying it out along the
mesh; feeding the shards straight from a sharded feature store is a
ROADMAP item.)

A request batch of B users shares the mesh: ``V (B, D, M)`` keeps the
candidate axis sharded (every device holds a ``(B, D, M/P)`` block) and
the per-slate SPMD body is ``vmap``-ed *inside* the ``shard_map``, so
the loop state becomes ``(B, Mloc)`` per device and each step's argmax
allreduce and winner broadcast move ``B`` values in one batched
collective instead of ``B`` sequential ones.

Per greedy step, inside one ``shard_map``:

1. **local update** — each device updates its candidate shard
   (O(D M / P) exact, O(w M / P) windowed) through the same tiled,
   double-buffered Pallas pass as the single-device streaming kernel
   (``repro.kernels.dpp_greedy.tiled``).  The per-device tile is an
   explicit ``tile_m=``, or else worked out from the platform and the
   shard's shape as on one chip (``_mesh_tile``); where Pallas runs
   interpreted (off a TPU) the update lowers through jnp step bodies
   whose products of V run at ``Precision.HIGHEST``;
2. **global argmax** — an all-gather allreduce of per-device
   ``(d2_max, global_index)`` pairs (P tiny pairs), first-occurrence
   tie-breaking identical to a single-device ``argmax``;
3. **winner broadcast** — one psum replicates the winning column's data
   (``V[:, j]``, its Cholesky column ``c_j`` and, windowed, the repaired
   ``d2[j]``) from the owner shard to everyone.

The sliding-window variant additionally psum-gathers the tiny ``(w, w)``
window factor ``C[:, win]`` each step so every device computes the same
Givens eviction rotations from the same bits.  The selected slate
matches the single-device ``dpp_greedy_lowrank`` /
``dpp_greedy_windowed_lowrank`` paths on the gathered ``V`` index for
index (same argmax sequence, same tie-breaking); the marginal-gain
history agrees to ~1 ulp — XLA may compile the per-shard ``(D, M/P)``
reductions with a different op order than the ``(D, M)`` shapes.

Front doors: ``greedy_map(GreedySpec(backend="sharded", mesh=...))``
dispatches here; serving goes through ``repro.serving.Reranker`` with
``cfg.mesh`` set (which also replaces the single-device
``jax.lax.top_k`` shortlist with ``sharded_topk``); the
``repro.launch.serve_sharded`` driver and ``benchmarks/fig5_sharded.py``
demonstrate the path end to end on a host-device mesh.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.greedy_chol import NEG_INF, GreedyResult
from repro.kernels.dpp_greedy.tiling import (
    LANE,
    auto_tile,
    plan_tile,
    round_up,
    tile_vmem_bytes,
    validate_tile_m,
)
from repro.kernels.platform import resolve_interpret
from repro.obs.dispatch import (
    record_kernel_dispatch,
    record_sharded_merges,
    record_tile_resolution,
)

# every product of V in the jnp step bodies: a TPU's default float32
# matmul rounds its operands through bfloat16
_HI = jax.lax.Precision.HIGHEST


def _mesh_axis_size(mesh, axis_name: str) -> int:
    if axis_name not in mesh.shape:
        raise ValueError(
            f"mesh has no axis {axis_name!r}; mesh axes: {tuple(mesh.shape)}"
        )
    return mesh.shape[axis_name]


def _global_argmax(d2, ax, off, axis_name):
    """(d2_max, global index, owner?) via a P-pair all-gather allreduce.

    Gathered in axis-index order, so ``argmax`` over the per-device maxima
    breaks ties toward the lowest shard — combined with the local
    ``argmax``'s first-occurrence rule this reproduces a single-device
    ``argmax`` over the concatenated candidate axis exactly.

    ``ax``/``off`` (axis index, shard offset) are computed once outside
    the greedy loop and passed in: a ``jax.lax.axis_index`` *inside* a
    ``fori_loop`` body can survive XLA simplification as a raw
    PartitionId op the SPMD partitioner rejects (observed on jax 0.4.x
    when the w=1 eviction loop folds away).
    """
    with jax.named_scope("sharded.merge"):
        jl = jnp.argmax(d2).astype(jnp.int32)
        dv = jax.lax.all_gather(d2[jl], axis_name)  # (P,)
        gv = jax.lax.all_gather(jl + off, axis_name)
        p = jnp.argmax(dv)
        return jl, dv[p], gv[p], p == ax


def _bcast_from_owner(parts, owner, axis_name):
    """Replicate the owner shard's small vectors to every device (one psum)."""
    with jax.named_scope("sharded.merge"):
        z = jnp.concatenate([jnp.atleast_1d(x) for x in parts])
        return jax.lax.psum(jnp.where(owner, z, jnp.zeros_like(z)), axis_name)


def _exact_step_fn(
    eps: float, axis_name: str,
    tile_m: Optional[int] = None,
):
    """Per-step body of sharded Algorithm 1, factored out so the
    whole-slate loop and the chunked streaming executor run the
    identical op sequence (streamed chunks concatenate exactly to the
    whole-slate slate).

    Returns ``step(t, Vl, ax, off, C, d2, stopped) ->
    (C, d2, stopped, j, dj)``; the jnp flavor keeps the column layout
    ``C (Mloc, k)``, the tiled flavor the row layout ``(k, Mloc)`` the
    Pallas pass streams."""

    def step_tiled(t, Vl, ax, off, C, d2, stopped):
        from repro.kernels.dpp_greedy.tiled import tiled_update_exact

        D = Vl.shape[0]
        eps2 = jnp.asarray(eps, Vl.dtype) ** 2
        jl, dj2, j, owner = _global_argmax(d2, ax, off, axis_name)
        stopped = stopped | (dj2 <= eps2)
        dj = jnp.sqrt(jnp.maximum(dj2, eps2))
        # winner broadcast: V[:, j] and its Cholesky column c_j
        z = _bcast_from_owner((Vl[:, jl], C[:, jl]), owner, axis_name)
        vj, cj = z[:D], z[D:]
        e, d2 = tiled_update_exact(
            Vl, C, d2, vj, cj, dj, stopped, j, off,
            tile_m=tile_m,
        )
        C = C.at[t].set(e)
        return C, d2, stopped, j, dj

    def step(t, Vl, ax, off, C, d2, stopped):
        D = Vl.shape[0]
        eps2 = jnp.asarray(eps, Vl.dtype) ** 2
        jl, dj2, j, owner = _global_argmax(d2, ax, off, axis_name)
        stopped = stopped | (dj2 <= eps2)
        dj = jnp.sqrt(jnp.maximum(dj2, eps2))
        # winner broadcast: V[:, j] and its Cholesky column c_j
        z = _bcast_from_owner((Vl[:, jl], C[jl, :]), owner, axis_name)
        vj, cj = z[:D], z[D:]
        # local shard of the update (eqs. 16-18): e = (L_j - c c_j) / d_j
        e = (jnp.matmul(vj, Vl, precision=_HI)
             - jnp.matmul(C, cj, precision=_HI)) / dj
        e = jnp.where(stopped, jnp.zeros_like(e), e)
        C = C.at[:, t].set(e)
        d2_next = d2 - e * e
        d2_next = d2_next.at[jl].set(jnp.where(owner, NEG_INF, d2_next[jl]))
        d2 = jnp.where(stopped, d2, d2_next)
        return C, d2, stopped, j, dj

    return step_tiled if tile_m is not None else step


def _exact_body(
    k: int, eps: float, axis_name: str,
    tile_m: Optional[int] = None,
):
    """Algorithm 1 with the candidate axis sharded; mirrors
    ``greedy_chol._greedy_loop`` operation-for-operation on each shard.

    With ``tile_m`` set, the local per-step update (the O(D M/P) matvec
    + Cholesky append + d2 downdate) runs through the same tiled Pallas
    pass as the single-device streaming kernel
    (``kernels.dpp_greedy.tiled.tiled_update_exact``) — the shard's
    global column offset makes the winner masking land on the owner —
    so an M/P shard past the VMEM budget streams in double-buffered
    tiles instead of lowering through unfused jnp."""
    step = _exact_step_fn(eps, axis_name, tile_m)
    # row layout (k, Mloc) for the tiled pass, column layout (Mloc, k)
    # for jnp — the latter kept so the reduction order (and therefore
    # d_hist) stays bitwise identical to the single-device path
    row_layout = tile_m is not None

    def body_fn(Vl, maskl):
        Mloc = Vl.shape[1]
        dtype = Vl.dtype
        ax = jax.lax.axis_index(axis_name)
        off = ax.astype(jnp.int32) * Mloc

        diag = jnp.sum(Vl * Vl, axis=0)
        d2 = jnp.where(maskl, diag, NEG_INF)
        C = jnp.zeros((k, Mloc) if row_layout else (Mloc, k), dtype)
        sel = jnp.full((k,), -1, jnp.int32)
        d_hist = jnp.zeros((k,), dtype)

        def body(t, state):
            C, d2, sel, d_hist, stopped = state
            C, d2, stopped, j, dj = step(t, Vl, ax, off, C, d2, stopped)
            sel = sel.at[t].set(jnp.where(stopped, -1, j))
            d_hist = d_hist.at[t].set(jnp.where(stopped, 0.0, dj))
            return C, d2, sel, d_hist, stopped

        state = (C, d2, sel, d_hist, jnp.asarray(False))
        _, _, sel, d_hist, _ = jax.lax.fori_loop(0, k, body, state)
        return sel, jnp.sum(sel >= 0).astype(jnp.int32), d_hist

    return body_fn


def _windowed_body(
    k: int, window: int, eps: float, axis_name: str,
    tile_m: Optional[int] = None,
):
    """Sliding-window greedy with the candidate axis sharded; mirrors
    ``windowed._windowed_loop``.

    The eviction Givens rotations read the window factor ``C[:, win]``
    — w columns scattered across shards — so each step psum-gathers that
    tiny ``(w, w)`` block first and every device then applies identical
    rotations to its local rows (and to the gathered block, which tracks
    the window columns through the loop).

    With ``tile_m`` set, the rotation coefficients are instead
    precomputed from the replicated ``(w, w)`` factor
    (``kernels.dpp_greedy.tiled.eviction_coeffs`` — the identical
    recurrence, factored out of the row sweep), the winner's
    post-eviction column and repaired ``d2[j]`` are derived from its
    broadcast *pre*-eviction column the same way, and the whole local
    evict + append lands in one ``tiled_update_windowed`` Pallas sweep
    over the shard.
    """
    w = min(window, k)
    step = _windowed_step_fn(w, eps, axis_name, tile_m)

    def body_fn(Vl, maskl):
        Mloc = Vl.shape[1]
        dtype = Vl.dtype
        ax = jax.lax.axis_index(axis_name)
        off = ax.astype(jnp.int32) * Mloc

        diag = jnp.sum(Vl * Vl, axis=0)
        d2 = jnp.where(maskl, diag, NEG_INF)
        C = jnp.zeros((w, Mloc), dtype)
        win = jnp.full((w,), -1, jnp.int32)  # window order: 0 = oldest
        sel = jnp.full((k,), -1, jnp.int32)
        d_hist = jnp.zeros((k,), dtype)

        def body(t, state):
            C, d2, win, sel, d_hist, stopped = state
            C, d2, win, stopped, j, dj = step(
                t, Vl, ax, off, C, d2, win, stopped
            )
            sel = sel.at[t].set(jnp.where(stopped, -1, j))
            d_hist = d_hist.at[t].set(jnp.where(stopped, 0.0, dj))
            return C, d2, win, sel, d_hist, stopped

        state = (C, d2, win, sel, d_hist, jnp.asarray(False))
        _, _, _, sel, d_hist, _ = jax.lax.fori_loop(0, k, body, state)
        return sel, jnp.sum(sel >= 0).astype(jnp.int32), d_hist

    return body_fn


def _windowed_step_fn(
    w: int, eps: float, axis_name: str,
    tile_m: Optional[int] = None,
):
    """Per-step body of the sharded sliding-window greedy, factored out
    so the whole-slate loop and the chunked streaming executor run the
    identical op sequence.  Returns
    ``step(t, Vl, ax, off, C, d2, win, stopped) ->
    (C, d2, win, stopped, j, dj)`` on the ring layout ``C (w, Mloc)``.
    """

    def step_tiled(t, Vl, ax, off, C, d2, win, stopped):
        from repro.kernels.dpp_greedy.tiled import (
            eviction_coeffs,
            tiled_update_windowed,
        )

        D, Mloc = Vl.shape
        eps2 = jnp.asarray(eps, Vl.dtype) ** 2
        win0 = win
        jl, dj2, j, owner = _global_argmax(d2, ax, off, axis_name)
        stopped = stopped | (dj2 <= eps2)
        dj = jnp.sqrt(jnp.maximum(dj2, eps2))

        # replicate the (w, w) window factor and the winner's
        # PRE-eviction column; everything data-dependent but small
        # is resolved here, between sweeps
        li = win - off
        owned = (win >= 0) & (li >= 0) & (li < Mloc)
        cols = jnp.take(C, jnp.clip(li, 0, Mloc - 1), axis=1)
        Cw = jax.lax.psum(
            jnp.where(owned[None, :], cols, jnp.zeros_like(cols)),
            axis_name,
        )
        z = _bcast_from_owner((Vl[:, jl], C[:, jl]), owner, axis_name)
        vj, cj_pre = z[:D], z[D:]
        full = jnp.logical_and(t >= w, jnp.logical_not(stopped))
        cos, sin, cj_post, d2j = eviction_coeffs(Cw, cj_pre, dj2, full, w)
        djp = jnp.sqrt(jnp.maximum(d2j, eps2))
        pos = jnp.minimum(t, w - 1)
        C, d2 = tiled_update_windowed(
            Vl, C, d2, vj, cj_post, djp, stopped, full, cos, sin,
            j, off, pos, w=w, tile_m=tile_m,
        )
        win_shift = jnp.roll(win, -1)
        win1 = jnp.where(full, win_shift.at[w - 1].set(-1), win)
        win = jnp.where(stopped, win0, win1.at[pos].set(j))
        return C, d2, win, stopped, j, dj

    def step(t, Vl, ax, off, C, d2, win, stopped):
        D, Mloc = Vl.shape
        dtype = Vl.dtype
        eps2 = jnp.asarray(eps, dtype) ** 2
        tiny = jnp.asarray(1e-30, dtype)
        C0, d20, win0 = C, d2, win

        jl, dj2, j, owner = _global_argmax(d2, ax, off, axis_name)
        stopped = stopped | (dj2 <= eps2)
        dj = jnp.sqrt(jnp.maximum(dj2, eps2))

        # ---- gather the (w, w) window factor C[:, win] from the
        # owner shard of each window member (one psum)
        li = win - off
        owned = (win >= 0) & (li >= 0) & (li < Mloc)
        cols = jnp.take(C, jnp.clip(li, 0, Mloc - 1), axis=1)  # (w, w)
        Cw = jax.lax.psum(
            jnp.where(owned[None, :], cols, jnp.zeros_like(cols)), axis_name
        )

        # ---- evict the oldest window item (window full only): the
        # same first-row Cholesky downdate as the single-device path,
        # with rotation coefficients read from the replicated Cw
        full = jnp.logical_and(t >= w, jnp.logical_not(stopped))
        u = jnp.where(full, C[0], jnp.zeros((Mloc,), dtype))
        u_w = jnp.where(full, Cw[0], jnp.zeros((w,), dtype))
        win_shift = jnp.roll(win, -1)

        def rot(r, carry):
            C, u, Cw, u_w = carry
            read = jnp.where(full, r + 1, r)
            row = jax.lax.dynamic_slice(C, (read, 0), (1, Mloc))[0]
            row_w = jax.lax.dynamic_slice(Cw, (read, 0), (1, w))[0]
            a = row_w[r + 1]  # = C[read, win_shift[r]] when full
            b = u_w[r + 1]
            rho = jnp.maximum(jnp.sqrt(a * a + b * b), tiny)
            cos = jnp.where(full, a / rho, 1.0)
            sin = jnp.where(full, b / rho, 0.0)
            new_row = cos * row + sin * u
            new_row_w = cos * row_w + sin * u_w
            u = cos * u - sin * row
            u_w = cos * u_w - sin * row_w
            C = jax.lax.dynamic_update_slice(C, new_row[None], (r, 0))
            Cw = jax.lax.dynamic_update_slice(Cw, new_row_w[None], (r, 0))
            return C, u, Cw, u_w

        C, u, _, _ = jax.lax.fori_loop(0, w - 1, rot, (C, u, Cw, u_w))
        C = jnp.where(full, C.at[w - 1].set(0.0), C)
        d2 = jnp.where(full, d2 + u * u, d2)
        win = jnp.where(full, win_shift.at[w - 1].set(-1), win)

        # ---- append j against the post-eviction window: broadcast
        # V[:, j], the post-eviction c_j and the repaired d2[j]
        z = _bcast_from_owner(
            (Vl[:, jl], C[:, jl], d2[jl]), owner, axis_name
        )
        vj, cj, d2j = z[:D], z[D : D + w], z[D + w]
        djp = jnp.sqrt(jnp.maximum(d2j, eps2))
        e = (jnp.matmul(vj, Vl, precision=_HI)
             - jnp.matmul(cj, C, precision=_HI)) / djp
        pos = jnp.minimum(t, w - 1)
        C_next = jax.lax.dynamic_update_slice(C, e[None], (pos, 0))
        d2_next = d2 - e * e
        d2_next = d2_next.at[jl].set(jnp.where(owner, NEG_INF, d2_next[jl]))
        win_next = win.at[pos].set(j)

        C = jnp.where(stopped, C0, C_next)
        d2 = jnp.where(stopped, d20, d2_next)
        win = jnp.where(stopped, win0, win_next)
        return C, d2, win, stopped, j, dj

    return step_tiled if tile_m is not None else step


# Compiled shard_map callables, keyed by (mesh, axis_name, static args).
# jax meshes hash by device assignment, so reuse across calls is exact
# and jit handles per-shape retracing underneath; the cache is bounded
# so long-lived servers sweeping k/window/eps don't grow it forever.
@functools.lru_cache(maxsize=64)
def _greedy_fn(
    mesh, axis_name: str, k: int, window: Optional[int], eps: float,
    batched: bool = False, tile_m: Optional[int] = None,
):
    if window is None:
        body = _exact_body(k, eps, axis_name, tile_m)
    else:
        body = _windowed_body(k, window, eps, axis_name, tile_m)
    if batched:
        # vmap inside shard_map: every device runs all B users on its
        # (B, D, Mloc) block and the per-step collectives batch over B
        body = jax.vmap(body)
        in_specs = (P(None, None, axis_name), P(None, axis_name))
    else:
        in_specs = (P(None, axis_name), P(axis_name))
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
    )


# ---------------------------------------------------------------------------
# Resumable streaming execution (chunk-emitting; repro.core.streaming)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _stream_init_fn(mesh, axis_name: str, batched: bool = False):
    """d2 initialization as a shard_map so the per-shard reduction order
    matches the whole-slate body bit for bit."""

    def body(Vl, maskl):
        diag = jnp.sum(Vl * Vl, axis=0)
        return jnp.where(maskl, diag, NEG_INF)

    if batched:
        body = jax.vmap(body)
        in_specs = (P(None, None, axis_name), P(None, axis_name))
        out_specs = P(None, axis_name)
    else:
        in_specs = (P(None, axis_name), P(axis_name))
        out_specs = P(axis_name)
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=64)
def _stream_chunk_fn(
    mesh, axis_name: str, chunk: int, w: Optional[int], eps: float,
    batched: bool = False, tile_m: Optional[int] = None,
    t_batched: bool = False,
):
    """Compiled shard_map advancing ``chunk`` greedy steps on resumable
    sharded state.  The per-device loop body is built from the same step
    factories as the whole-slate ``_greedy_fn``, so a sequence of chunks
    reproduces the whole-slate selection exactly; between chunks the
    C/d2 shards stay device-resident and only the (chunk,)-sized
    sel/d_hist (plus the replicated ring/stop scalars) reach the host —
    one collective round per chunk, not per slate."""
    windowed = w is not None

    if windowed:
        step = _windowed_step_fn(w, eps, axis_name, tile_m)

        def body(Vl, C, d2, win, stopped, t0):
            Mloc = Vl.shape[1]
            ax = jax.lax.axis_index(axis_name)
            off = ax.astype(jnp.int32) * Mloc
            sel = jnp.full((chunk,), -1, jnp.int32)
            dh = jnp.zeros((chunk,), d2.dtype)

            def sbody(s, carry):
                C, d2, win, stopped, sel, dh = carry
                C, d2, win, stopped, j, dj = step(
                    t0 + s, Vl, ax, off, C, d2, win, stopped
                )
                sel = sel.at[s].set(jnp.where(stopped, -1, j))
                dh = dh.at[s].set(jnp.where(stopped, 0.0, dj))
                return C, d2, win, stopped, sel, dh

            return jax.lax.fori_loop(
                0, chunk, sbody, (C, d2, win, stopped, sel, dh)
            )

        c_spec = P(None, axis_name)
        state_in = (c_spec, P(axis_name), P(), P())
        state_out = (c_spec, P(axis_name), P(), P())
    else:
        step = _exact_step_fn(eps, axis_name, tile_m)

        def body(Vl, C, d2, stopped, t0):
            Mloc = Vl.shape[1]
            ax = jax.lax.axis_index(axis_name)
            off = ax.astype(jnp.int32) * Mloc
            sel = jnp.full((chunk,), -1, jnp.int32)
            dh = jnp.zeros((chunk,), d2.dtype)

            def sbody(s, carry):
                C, d2, stopped, sel, dh = carry
                C, d2, stopped, j, dj = step(
                    t0 + s, Vl, ax, off, C, d2, stopped
                )
                sel = sel.at[s].set(jnp.where(stopped, -1, j))
                dh = dh.at[s].set(jnp.where(stopped, 0.0, dj))
                return C, d2, stopped, sel, dh

            return jax.lax.fori_loop(
                0, chunk, sbody, (C, d2, stopped, sel, dh)
            )

        # row layout (k, Mloc) for the tiled pass, column layout
        # (Mloc, k) for jnp — as in the whole-slate bodies
        c_spec = P(None, axis_name) if tile_m is not None else P(axis_name, None)
        state_in = (c_spec, P(axis_name), P())
        state_out = (c_spec, P(axis_name), P())

    if batched:
        nstate = len(state_in)
        # t_batched: the continuous-batching slot layout carries a
        # per-slot step counter t (B,) (slots join mid-flight at
        # heterogeneous progress — repro.core.streaming slot executors);
        # the uniform batch paths keep the shared scalar
        body = jax.vmap(
            body, in_axes=(0,) * (1 + nstate) + (0 if t_batched else None,)
        )
        bat = lambda spec: P(None, *spec)
        in_specs = (
            (P(None, None, axis_name),)
            + tuple(bat(s) for s in state_in)
            + (P(None) if t_batched else P(),)
        )
        out_specs = tuple(bat(s) for s in state_out) + (
            P(None, None), P(None, None),
        )
    else:
        in_specs = (P(None, axis_name),) + state_in + (P(),)
        out_specs = state_out + (P(), P())
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )


def _mesh_tile(tile_m, D, Mloc, state_rows, windowed):
    """The per-device tile of the step bodies on a shard of ``Mloc``
    candidates, and how it was chosen: ``(tile or None, source)``; a
    None tile runs the jnp step bodies.

    An explicit ``tile_m`` wins.  Unset, the platform and the shard's
    shape decide, as on one chip (``plan_tile``).  Where Pallas
    runs interpreted (off a TPU) the jnp bodies run.  Where it runs
    compiled, the model's ``tiled`` answer gives its tile; ``resident``
    (the shard fits on-core, and the mesh has no resident kernel) one
    tile over the whole shard, or the widest the per-tile budget admits;
    ``jnp`` (not even one lane-width tile fits) the jnp bodies."""
    if tile_m is not None:
        return tile_m, "explicit"
    if resolve_interpret():
        return None, None
    mode, tm = plan_tile(D, Mloc, state_rows, windowed)
    if mode == "resident":
        tm = min(round_up(Mloc, LANE), auto_tile(D, state_rows, windowed))
    return tm or None, "model"


def padded_candidates(M: int, nshards: int, tile_m: Optional[int]) -> int:
    """The candidate axis the mesh runs: ``M`` zero-padded up to a
    multiple of ``nshards`` times the per-device tile."""
    quantum = nshards * (tile_m or 1)
    return -(-M // quantum) * quantum


def _record_dispatch(D, M, state_rows, windowed, tile_m, source):
    """Count the per-device update path that runs (the tiled Pallas
    pass, or the jnp step bodies) and how its tile was chosen."""
    record_kernel_dispatch(
        "tiled" if tile_m is not None else "jnp", D=D, M=M,
        state_rows=state_rows, windowed=windowed, tile_m=tile_m,
        interpret=resolve_interpret(),
        vmem_bytes=(None if tile_m is None
                    else tile_vmem_bytes(D, tile_m, state_rows, windowed)),
    )
    if source is not None:
        record_tile_resolution(source)


def _stream_pad(V, Mp):
    M = V.shape[-1]
    if Mp == M:
        return V
    pad = [(0, 0)] * (V.ndim - 1) + [(0, Mp - M)]
    return jnp.pad(V, pad)


def dpp_greedy_sharded_stream_init(
    V: jnp.ndarray,
    k: int,
    *,
    mesh,
    axis_name: str = "data",
    window: Optional[int] = None,
    mask: Optional[jnp.ndarray] = None,
    tile_m: Optional[int] = None,
):
    """Initial resumable state for the sharded streaming path.

    Same contract as ``dpp_greedy_sharded`` (V (D, M) / (B, D, M),
    mask broadcastable, M padded to the mesh/tile quantum); returns a
    ``repro.core.streaming.GreedyState`` whose C/d2 leaves are the
    *global* views of the per-device slices (layouts as the whole-slate
    bodies use: exact jnp ``(M, k)`` columns, exact tiled ``(k, M)``
    rows, windowed ``(w, M)`` ring).
    """
    from repro.core.streaming import GreedyState

    if V.ndim not in (2, 3):
        raise ValueError(
            f"sharded streaming takes V (D, M) or a user batch (B, D, M), "
            f"got ndim={V.ndim}"
        )
    if k <= 0:
        raise ValueError(f"k must be >= 1, got {k}")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    validate_tile_m(tile_m)
    batched = V.ndim == 3
    B = V.shape[0] if batched else None
    nshards = _mesh_axis_size(mesh, axis_name)
    M = V.shape[-1]
    mask_shape = (B, M) if batched else (M,)
    if mask is None:
        mask = jnp.ones(mask_shape, bool)
    elif mask.shape != mask_shape:
        mask = jnp.broadcast_to(mask, mask_shape)
    windowed = window is not None and window < k
    R = min(window, k) if windowed else k
    tile_m, source = _mesh_tile(tile_m, V.shape[-2], -(-M // nshards), R,
                                windowed)
    _record_dispatch(V.shape[-2], M, R, windowed, tile_m, source)
    Mp = padded_candidates(M, nshards, tile_m)
    V = _stream_pad(V, Mp)
    if Mp != M:
        mask = jnp.pad(
            mask, [(0, 0)] * (mask.ndim - 1) + [(0, Mp - M)],
            constant_values=False,
        )
    d2 = _stream_init_fn(mesh, axis_name, batched)(V, mask)
    dtype = V.dtype
    lead = (B,) if batched else ()
    if windowed:
        C = jnp.zeros(lead + (R, Mp), dtype)
        win = jnp.full(lead + (R,), -1, jnp.int32)
    else:
        shape = (k, Mp) if tile_m is not None else (Mp, k)
        C = jnp.zeros(lead + shape, dtype)
        win = jnp.zeros(lead + (0,), jnp.int32)
    stopped = jnp.zeros(lead, bool) if batched else jnp.asarray(False)
    return GreedyState(jnp.zeros((), jnp.int32), stopped, C, d2, win)


def dpp_greedy_sharded_stream_chunk(
    V: jnp.ndarray,
    state,
    chunk: int,
    *,
    mesh,
    axis_name: str = "data",
    eps: float = 1e-6,
    tile_m: Optional[int] = None,
):
    """Advance ``chunk`` sharded greedy steps on a resumable state.

    The state is authoritative for the mode (its ``win`` leaf decides
    windowed vs exact).  Returns ``(state, sel, dh)`` — ``sel``/``dh``
    shaped ``(chunk,)`` single / ``(B, chunk)`` batched, global
    candidate ids.  Chunks concatenate exactly to
    ``dpp_greedy_sharded``'s whole-slate result.

    A batched state may carry either the shared scalar step counter
    ``t ()`` (uniform batch — every lane started together) or a
    per-slot ``t (B,)`` (the continuous-batching slot layout, where
    requests join and leave mid-flight; see the slot executors in
    ``repro.core.streaming``) — the per-device step bodies consume
    ``t`` per lane either way.
    """
    batched = V.ndim == 3
    Mp = state.d2.shape[-1]
    V = _stream_pad(V, Mp)
    windowed = state.win.shape[-1] > 0
    w = state.win.shape[-1] if windowed else None
    # the state's rows: the ring's w, or k in either exact layout (rows
    # (k, Mp) on the tiled pass, columns (Mp, k) on the jnp bodies)
    R = w if windowed else (
        state.C.shape[-2] if state.C.shape[-1] == Mp else state.C.shape[-1])
    nshards = _mesh_axis_size(mesh, axis_name)
    tile_m, _ = _mesh_tile(tile_m, V.shape[-2], Mp // nshards, R, windowed)
    record_sharded_merges(chunk, windowed=windowed)
    t_batched = batched and jnp.ndim(state.t) == 1
    fn = _stream_chunk_fn(
        mesh, axis_name, chunk, w, float(eps), batched, tile_m, t_batched,
    )
    if windowed:
        C, d2, win, stopped, sel, dh = fn(
            V, state.C, state.d2, state.win, state.stopped, state.t
        )
    else:
        C, d2, stopped, sel, dh = fn(
            V, state.C, state.d2, state.stopped, state.t
        )
        win = state.win
    new_state = type(state)(state.t + chunk, stopped, C, d2, win)
    return new_state, sel, dh


class MeshGreedy(NamedTuple):
    """One mesh greedy resolved on the host for its shapes: the compiled
    ``shard_map`` (``_greedy_fn``) and the candidate axis it runs, ``M``
    padded to the mesh/tile quantum (``padded_candidates``)."""

    fn: Callable
    padded: int


def plan_greedy(
    D: int,
    M: int,
    k: int,
    *,
    mesh,
    axis_name: str = "data",
    window: Optional[int] = None,
    eps: float = 1e-6,
    tile_m: Optional[int] = None,
    batched: bool = False,
) -> MeshGreedy:
    """The host part of ``dpp_greedy_sharded``: validate, resolve the
    per-device tile (``_mesh_tile``) and count the dispatch and its
    merges, from static shapes, once per call.  ``run_greedy`` then runs
    the plan, inside or outside a ``jit``."""
    if k <= 0:
        raise ValueError(f"k must be >= 1, got {k}")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    validate_tile_m(tile_m)
    nshards = _mesh_axis_size(mesh, axis_name)
    window_eff = window if (window is not None and window < k) else None
    windowed = window_eff is not None
    R = window_eff if windowed else k
    tile_m, source = _mesh_tile(tile_m, D, -(-M // nshards), R, windowed)
    _record_dispatch(D, M, R, windowed, tile_m, source)
    record_sharded_merges(k, windowed=windowed)
    fn = _greedy_fn(mesh, axis_name, k, window_eff, float(eps), batched,
                    tile_m)
    return MeshGreedy(fn, padded_candidates(M, nshards, tile_m))


def run_greedy(plan: MeshGreedy, V: jnp.ndarray,
               mask: Optional[jnp.ndarray] = None) -> GreedyResult:
    """The traceable part of ``dpp_greedy_sharded``: the default mask,
    the zero pad of ``V`` and the mask up to ``plan.padded`` (nothing
    where they arrive padded), and the compiled ``shard_map``."""
    M = V.shape[-1]
    mask_shape = V.shape[:-2] + (M,)
    if mask is None:
        mask = jnp.ones(mask_shape, bool)
    elif mask.shape != mask_shape:
        mask = jnp.broadcast_to(mask, mask_shape)
    if plan.padded != M:
        pad = [(0, 0)] * (V.ndim - 1) + [(0, plan.padded - M)]
        V = jnp.pad(V, pad)
        mask = jnp.pad(mask, pad[1:], constant_values=False)
    sel, n, d_hist = plan.fn(V, mask)
    return GreedyResult(sel, n, d_hist)


def dpp_greedy_sharded(
    V: jnp.ndarray,
    k: int,
    *,
    mesh,
    axis_name: str = "data",
    window: Optional[int] = None,
    eps: float = 1e-6,
    mask: Optional[jnp.ndarray] = None,
    tile_m: Optional[int] = None,
) -> GreedyResult:
    """Greedy DPP MAP with the candidate axis of ``V`` sharded.

    ``V`` is a single problem ``(D, M)`` or a user batch ``(B, D, M)``;
    ``mask`` is ``(M,)``, ``(B, M)``, or — batched with a shared
    candidate filter — ``(M,)`` broadcast over B.  Selects the same
    slate(s) — identical indices, d_hist equal to ~1 ulp — as
    ``dpp_greedy_lowrank`` (``window=None`` / ``>= k``) or
    ``dpp_greedy_windowed_lowrank`` (smaller windows), respectively
    their ``_batch`` vmap variants, on the gathered ``V``; but each
    device's compute only touches its ``(D, M/P)`` (or ``(B, D, M/P)``)
    shard where ``P = mesh.shape[axis_name]``.  ``M`` is zero-padded
    (mask False) up to a multiple of ``P`` times the tile; padding can
    never be selected.

    The index-for-index match holds while marginal gains sit above the
    float32 cancellation-noise floor; past the kernel's numerical rank
    (``k`` beyond ~``D`` selections) the argmax runs on rounding noise
    on any backend — set ``eps`` to stop there (paper eq. 20), as the
    single-device paths also should.

    Each device's local per-step update runs the tiled streaming
    Pallas pass (``repro.kernels.dpp_greedy.tiled``), the kernel the
    single-device tiled path runs, in tile-column blocks.  ``tile_m``
    sets the tile; unset, it is worked out from the platform and the
    shard's shape (``_mesh_tile``): on a TPU the VMEM model's tile, off
    one the jnp step bodies (the Pallas interpreter would only be
    slower).  ``M`` is padded up to a multiple of ``P * tile``, so a
    wide tile adds padded columns.

    Composed of ``plan_greedy`` (host) and ``run_greedy`` (traceable),
    which a caller that compiles the greedy into a program of its own
    calls apart (``serving.api._sharded_rerank_impl``).
    """
    if V.ndim not in (2, 3):
        raise ValueError(
            f"dpp_greedy_sharded takes V (D, M) or a user batch (B, D, M), "
            f"got ndim={V.ndim}"
        )
    plan = plan_greedy(
        V.shape[-2], V.shape[-1], k, mesh=mesh, axis_name=axis_name,
        window=window, eps=eps, tile_m=tile_m, batched=V.ndim == 3,
    )
    return run_greedy(plan, V, mask)


@functools.lru_cache(maxsize=64)
def _topk_fn(mesh, axis_name: str, c: int, batched: bool = False):
    nsh = _mesh_axis_size(mesh, axis_name)
    # log(P) tree merge: recursive doubling over the hypercube — at
    # round r every device exchanges its current top-c with its
    # (axis ^ 2^r) partner and keeps the top-c of the union, so after
    # log2(P) rounds every device holds the exact global top-c having
    # moved P*log(P)*c values total instead of the all-gather's P^2*c
    # replicated payload.  Requires power-of-two P; other axis sizes
    # keep the all-gather merge.
    tree = nsh > 1 and (nsh & (nsh - 1)) == 0

    def body(s):
        Mloc = s.shape[0]
        off = jax.lax.axis_index(axis_name).astype(jnp.int32) * Mloc
        cl = min(c, Mloc)
        v, i = jax.lax.top_k(s, cl)
        gi = i.astype(jnp.int32) + off
        if not tree:
            av = jax.lax.all_gather(v, axis_name).reshape(-1)
            ai = jax.lax.all_gather(gi, axis_name).reshape(-1)
            vv, pp = jax.lax.top_k(av, c)
            return vv, ai[pp]
        if cl < c:  # pad local lists to a common length c
            v = jnp.concatenate([v, jnp.full((c - cl,), NEG_INF, v.dtype)])
            gi = jnp.concatenate(
                [gi, jnp.full((c - cl,), jnp.iinfo(jnp.int32).max, jnp.int32)]
            )
        # sort keys (-value, index): value-descending with lowest-global-
        # index tie-breaking — exactly the order (and tie winners)
        # jax.lax.top_k produces on the gathered vector, because each
        # local top_k already lists equal values by ascending index
        nv = -v
        for step in range(nsh.bit_length() - 1):
            d = 1 << step
            perm = [(p, p ^ d) for p in range(nsh)]
            pnv = jax.lax.ppermute(nv, axis_name, perm)
            pgi = jax.lax.ppermute(gi, axis_name, perm)
            snv, sgi = jax.lax.sort(
                (jnp.concatenate([nv, pnv]), jnp.concatenate([gi, pgi])),
                num_keys=2,
            )
            nv, gi = snv[:c], sgi[:c]
        return -nv, gi

    if batched:
        body = jax.vmap(body)
        in_specs = (P(None, axis_name),)
    else:
        in_specs = (P(axis_name),)
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), P()),
            check_vma=False,
        )
    )


def sharded_topk(scores: jnp.ndarray, c: int, *, mesh, axis_name: str = "data"):
    """Global top-c of a candidate-sharded score vector ``scores (M,)``
    or score batch ``(B, M)``.

    Each shard takes a local top-``min(c, M/P)``; the survivors then
    merge in ``log2(P)`` recursive-doubling rounds (pairwise
    ``lax.ppermute`` exchange + top-c reduce — exact, since every
    global top-c element survives its own shard's local top-c and
    top-c-of-unions preserves it), falling back to a single all-gather
    merge when P is not a power of two.  The sharded replacement for a
    single-device ``jax.lax.top_k`` shortlist.  Returns
    ``(values (c,), global indices (c,) int32)`` — leading B axis when
    batched — with the same value order and lowest-index tie-breaking
    as ``jax.lax.top_k`` on the gathered vector(s).
    """
    if scores.ndim not in (1, 2):
        raise ValueError(
            f"sharded_topk takes scores (M,) or a batch (B, M), "
            f"got ndim={scores.ndim}"
        )
    batched = scores.ndim == 2
    nshards = _mesh_axis_size(mesh, axis_name)
    M = scores.shape[-1]
    c = min(c, M)
    if c <= 0:
        raise ValueError(f"c must be >= 1, got {c}")
    Mp = -(-M // nshards) * nshards
    if Mp != M:
        pad = ((0, 0), (0, Mp - M)) if batched else ((0, Mp - M),)
        scores = jnp.pad(scores, pad, constant_values=NEG_INF)
    return _topk_fn(mesh, axis_name, c, batched)(scores)
