"""Jitted public wrapper for the dpp_greedy Pallas kernels.

Kernel-first dispatch (``tiling.plan_tile``): when the whole working set
``V (D, M)`` + Cholesky state fits the VMEM budget, the resident
whole-slate kernels in ``dpp_greedy.py`` run (the entire greedy loop in
one ``pallas_call``); past the budget the **tiled streaming kernels**
in ``tiled.py`` run instead — each greedy step is a double-buffered
grid sweep over ``(D, tile_m)`` / ``(state_rows, tile_m)`` blocks, so
large M no longer degrades to the pure-jnp path.  VMEM accounting is
per *tile* (``tiling.tile_vmem_bytes``); the resident-mode whole-array
working set is ``tiling.untiled_vmem_bytes`` (the pre-PR-4
``vmem_bytes`` shim over it is gone).

The pure-jnp reference remains reachable via ``force_jnp=True`` (and as
a last resort when even one lane-width tile would not fit — pathological
``D``/``state_rows``).

``window=w`` selects the sliding-window variants: the Cholesky state
shrinks from (k, M) to (w, M), so both the resident-mode budget check
and the per-tile model depend on ``w`` rather than the slate length.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.dpp_greedy.dpp_greedy import dpp_greedy_kernel
from repro.kernels.dpp_greedy.ref import dpp_greedy_ref
from repro.kernels.dpp_greedy.tiled import (
    dpp_greedy_tiled,
    fused_chunk_exact,
    fused_chunk_windowed,
)
from repro.kernels.dpp_greedy.tiling import (
    LANE,
    SUBLANE,
    plan_tile,
    round_up as _round_up,
    tile_vmem_bytes,
    untiled_vmem_bytes,
)
from repro.kernels.platform import resolve_interpret
from repro.obs.dispatch import record_kernel_dispatch, record_tile_resolution


def dpp_greedy_plan(
    D: int,
    M: int,
    k: int,
    window: int | None = None,
    tile_m: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """The kernel ``dpp_greedy`` runs for ``V (B, D, M)``, planned by
    ``plan_tile`` and recorded as one dispatch: ``(mode, tile_m,
    interpret)``.

    ``dpp_greedy`` calls it on every dispatch.  A caller that runs
    ``dpp_greedy`` inside a compiled program, whose body records only
    while it is traced, calls it on the host once per call instead."""
    state_rows = k if window is None else min(window, k)
    windowed = window is not None and window < k
    mode, tm = plan_tile(D, M, state_rows, windowed, tile_m=tile_m)
    record_tile_resolution("model" if tile_m is None else "explicit")
    interpret = resolve_interpret(interpret)
    record_kernel_dispatch(
        mode, D=D, M=M, state_rows=state_rows, windowed=windowed, tile_m=tm,
        interpret=interpret,
        vmem_bytes=(
            untiled_vmem_bytes(D, M, state_rows) if mode == "resident"
            else tile_vmem_bytes(D, tm, state_rows, windowed)
            if mode == "tiled" else None
        ),
    )
    return mode, tm, interpret


def dpp_greedy(
    V: jnp.ndarray,
    k: int,
    mask: jnp.ndarray | None = None,
    eps: float = 1e-3,
    interpret: Optional[bool] = None,
    force_jnp: bool = False,
    window: int | None = None,
    tile_m: Optional[int] = None,
):
    """Batched greedy DPP MAP inference.

    V (B, D, M) scaled features, mask (B, M). Returns (sel, d_hist) with
    shape (B, k); sel slots after an eps-stop hold -1.  ``window=w``
    enforces diversity only against the last w picks (O(w M) VMEM state,
    unbounded k); ``window >= k`` or None is the exact Algorithm 1.

    ``tile_m`` forces the tiled streaming kernels with that
    candidate-axis tile; by default ``plan_tile`` picks the resident
    kernels when the working set fits VMEM and the widest fitting tile
    otherwise.
    ``interpret`` defaults to the platform (compiled on a TPU,
    interpreted elsewhere — ``repro.kernels.platform``).
    """
    B, D, M = V.shape
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if mask is None:
        mask = jnp.ones((B, M), bool)
    if force_jnp:
        record_kernel_dispatch(
            "jnp", D=D, M=M, state_rows=k if window is None else min(window, k),
            windowed=window is not None and window < k,
        )
        return dpp_greedy_ref(V, mask, k, eps, window=window)

    mode, tm, interpret = dpp_greedy_plan(
        D, M, k, window, tile_m, interpret
    )
    if mode == "jnp":  # even a single lane-width tile exceeds the budget
        return dpp_greedy_ref(V, mask, k, eps, window=window)

    Dp = _round_up(D, SUBLANE)
    Mp = _round_up(M, LANE if mode == "resident" else tm)
    if (Mp, Dp) != (M, D):
        V = jnp.pad(V, ((0, 0), (0, Dp - D), (0, Mp - M)))
        mask = jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, Mp - M)))
    if mode == "resident":
        return dpp_greedy_kernel(
            V, mask, k=k, window=window, eps=eps, interpret=interpret
        )
    return dpp_greedy_tiled(
        V, mask, k, window=window, eps=eps, tile_m=min(tm, Mp),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Resumable streaming execution (chunk-emitting; repro.core.streaming)
# ---------------------------------------------------------------------------


def _stream_tile(D: int, M: int, state_rows: int, windowed: bool,
                 tile_m: Optional[int]):
    """The candidate-axis tile a streaming state uses, derived
    deterministically from the problem shape so init and every chunk
    agree.  Resident-size working sets run the fused chunk kernel as a
    single whole-M tile (the VMEM-resident analogue)."""
    # chunked=True: the fused chunk kernels stream the full Cholesky
    # block back out every step, so their per-tile working set is wider
    # than the per-step sweep the default model describes.
    mode, tm = plan_tile(D, M, state_rows, windowed, chunked=True,
                         tile_m=tile_m)
    record_tile_resolution("model" if tile_m is None else "explicit")
    if mode == "jnp":
        raise ValueError(
            "pathological shape: even one lane-width tile exceeds the VMEM "
            "budget — stream through the jnp backend instead"
        )
    if mode == "resident":
        Mp = _round_up(M, LANE)
        return Mp, Mp
    Mp = _round_up(M, tm)
    return min(tm, Mp), Mp


def dpp_greedy_stream_plan(D: int, M: int, state_rows: int, windowed: bool,
                           tile_m: Optional[int] = None):
    """The fused chunk kernel a streaming state of ``(D, M)`` runs,
    recorded as one dispatch: ``(tile, Mp)`` (see ``_stream_tile``).
    ``dpp_greedy_stream_init`` calls it; so does a caller that runs the
    stream inside a compiled program, on the host once per call."""
    tile, Mp = _stream_tile(D, M, state_rows, windowed, tile_m)
    record_kernel_dispatch(
        "fused_chunk", D=D, M=M, state_rows=state_rows, windowed=windowed,
        tile_m=tile, interpret=resolve_interpret(),
        vmem_bytes=tile_vmem_bytes(D, tile, state_rows, windowed,
                                   chunked=True),
    )
    return tile, Mp


def dpp_greedy_stream_init(
    V: jnp.ndarray,
    k: int,
    mask: jnp.ndarray | None = None,
    window: int | None = None,
    tile_m: Optional[int] = None,
):
    """Initial resumable state for the Pallas streaming path.

    V (D, M) single or (B, D, M) batched.  Returns a
    ``repro.core.streaming.GreedyState`` in the kernels' layout: padded
    row-layout Cholesky state ``C (B, R, Mp)``, ``d2 (B, Mp)`` with the
    mask (and padding) folded in, ``win (B, w)`` ring ids (``(B, 0)``
    exact), per-user ``stopped (B,)``.
    """
    from repro.core.streaming import GreedyState

    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    single = V.ndim == 2
    Vb = (V[None] if single else V).astype(jnp.float32)
    B, D, M = Vb.shape
    windowed = window is not None and window < k
    R = min(window, k) if windowed else k
    tile, Mp = dpp_greedy_stream_plan(D, M, R, windowed, tile_m)
    if mask is None:
        mask = jnp.ones((B, M), bool)
    elif mask.ndim == 1:
        mask = mask[None]
    Dp = _round_up(D, SUBLANE)
    if (Mp, Dp) != (M, D):
        Vb = jnp.pad(Vb, ((0, 0), (0, Dp - D), (0, Mp - M)))
        mask = jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, Mp - M)))
    diag = jnp.sum(Vb * Vb, axis=1)  # (B, Mp)
    d2 = jnp.where(mask > 0, diag, float("-inf"))
    C = jnp.zeros((B, R, Mp), jnp.float32)
    win = (
        jnp.full((B, R), -1, jnp.int32) if windowed
        else jnp.zeros((B, 0), jnp.int32)
    )
    return GreedyState(
        jnp.zeros((), jnp.int32), jnp.zeros((B,), bool), C, d2, win
    )


def dpp_greedy_stream_pad(V: jnp.ndarray, state) -> jnp.ndarray:
    """Pad/cast ``V`` once to the streaming state's (Dp, Mp) geometry.

    ``dpp_greedy_stream_chunk`` accepts raw ``V`` and pads on the fly,
    but that re-copies the full array every chunk; a generator looping
    many chunks should pad once up front (the chunk executor detects
    the already-padded shape and skips the copy) —
    ``repro.core.dispatch.greedy_map_chunks`` does this."""
    single = V.ndim == 2
    Vb = (V[None] if single else V).astype(jnp.float32)
    B, D, M = Vb.shape
    Mp = state.d2.shape[-1]
    Dp = _round_up(D, SUBLANE)
    if (Mp, Dp) != (M, D):
        Vb = jnp.pad(Vb, ((0, 0), (0, Dp - D), (0, Mp - M)))
    return Vb[0] if single else Vb


def dpp_greedy_stream_chunk(
    V: jnp.ndarray,
    state,
    chunk: int,
    *,
    eps: float = 1e-3,
    tile_m: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Advance ``chunk`` greedy steps on a Pallas streaming state.

    One fused ``pallas_call`` — one HBM C/d2 round-trip — per chunk
    (see ``repro.kernels.dpp_greedy.tiled``).  The state is
    authoritative for the mode (its ``win`` leaf decides windowed vs
    exact).  Returns ``(state, sel, dh)`` with ``sel``/``dh`` shaped
    ``(chunk,)`` for a single-problem ``V (D, M)`` and ``(B, chunk)``
    batched.

    ``state.t`` may be the shared scalar the uniform batch paths use
    or a per-lane ``(B,)`` counter (the continuous-batching slot
    layout of ``repro.core.streaming`` — slots join mid-flight at
    heterogeneous progress): the fused kernels carry ``t`` per grid
    lane in their ``stepi`` cells either way, so each lane's Cholesky
    row index / ring position follows its own counter.
    """
    single = V.ndim == 2
    Vb = (V[None] if single else V).astype(jnp.float32)
    B, D, M = Vb.shape
    windowed = state.win.shape[-1] > 0
    R = state.C.shape[1]
    tile, Mp = _stream_tile(D, M, R, windowed, tile_m)
    if Mp != state.d2.shape[-1]:
        raise ValueError(
            f"state was built for a padded candidate axis of "
            f"{state.d2.shape[-1]}, but V (M={M}) pads to {Mp} — "
            f"pass the same V/tile configuration used at init"
        )
    Dp = _round_up(D, SUBLANE)
    if (Mp, Dp) != (M, D):
        Vb = jnp.pad(Vb, ((0, 0), (0, Dp - D), (0, Mp - M)))
    if windowed:
        C, d2, win, stopped, sel, dh = fused_chunk_windowed(
            Vb, state.C, state.d2, state.win, state.t, state.stopped,
            chunk=chunk, eps=float(eps), w=R, tile_m=tile,
            interpret=interpret,
        )
    else:
        C, d2, stopped, sel, dh = fused_chunk_exact(
            Vb, state.C, state.d2, state.t, state.stopped,
            chunk=chunk, eps=float(eps), tile_m=tile, interpret=interpret,
        )
        win = state.win
    new_state = type(state)(state.t + chunk, stopped, C, d2, win)
    if single:
        return new_state, sel[0], dh[0]
    return new_state, sel, dh
