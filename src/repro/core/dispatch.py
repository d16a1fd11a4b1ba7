"""One front door for greedy DPP MAP inference.

Every greedy variant in the repo — exact Algorithm 1 (dense or low-rank,
single or batched), the sliding-window incremental variant, the Pallas
whole-slate-in-VMEM kernel, and the candidate-sharded multi-device path
— is reachable through ``greedy_map`` with a ``GreedySpec``.  The
serving reranker and the benchmark harness both dispatch through here,
so a config change (say, turning on a window for long feeds, or
spreading the candidate axis over a mesh) never requires touching call
sites.

Dispatch rules:

* kernel representation — pass exactly one of ``L`` (dense, (M, M) or
  (B, M, M)) or ``V`` (low-rank ``L = V^T V``, (D, M) or (B, D, M));
* ``spec.window`` — ``None`` (or ``>= k``) runs the exact Algorithm 1;
  smaller windows run the O(w M)-per-step incremental sliding-window
  greedy (unbounded slate length);
* ``spec.backend`` — "jnp" lowers through XLA; "pallas" routes low-rank
  inputs through the TPU kernels (compiled on a TPU, interpreted on
  other platforms; dense inputs are rejected — the kernels never
  materialize L); "sharded" shards the candidate axis M over
  ``spec.mesh``'s ``spec.axis_name`` (low-rank; batched V runs all B
  users on the mesh at once); "auto" picks "sharded" when a mesh is
  set, else "jnp";
* ``spec.tile_m`` — candidate-axis tile for the Pallas kernels.  On the
  pallas backend it forces the tiled streaming kernels (by default
  ``plan_tile`` keeps the whole-working-set resident kernels while
  they fit VMEM and tiles past that); on the sharded backend each
  device's local per-step update reuses the same tiled kernel on its
  (D, M/P) shard, and unset, the tile comes from the shard's shape on
  a TPU (``repro.core.sharded``).
* ``spec.chunk_size`` — greedy steps per resumable chunk.  On the
  pallas backend ``greedy_map`` then runs the slate as fused multi-step
  chunk kernels (one pallas_call — one HBM C/d2 round-trip — per
  chunk, the ROADMAP's sweep-fusion headroom); on the sharded backend
  the slate advances chunk-by-chunk with the loop state staying
  device-resident between chunks.  Both produce the identical slate to
  unchunked execution.  The pure-jnp whole-slate path has no chunked
  execution, so ``chunk_size`` with ``backend='jnp'`` (or ``'auto'``
  without a mesh) is rejected at construction — mirroring the
  ``tile_m`` rule; jnp *streaming* passes ``chunk_size=`` to
  ``greedy_map_chunks`` directly instead.

``greedy_map_chunks`` is the streaming front door: a generator yielding
per-chunk ``GreedyResult``s whose concatenation is exactly the
whole-slate ``greedy_map`` result (see ``repro.core.streaming``).

``GreedySpec`` validates itself at construction — a bad config raises
``GreedySpecError`` (a ``ValueError``) at spec-build time instead of
surfacing as a shape or trace error deep inside a jitted computation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from repro.core.greedy_chol import (
    GreedyResult,
    dpp_greedy_dense,
    dpp_greedy_dense_batch,
    dpp_greedy_lowrank,
    dpp_greedy_lowrank_batch,
)
from repro.core.windowed import (
    dpp_greedy_windowed,
    dpp_greedy_windowed_batch,
    dpp_greedy_windowed_lowrank,
    dpp_greedy_windowed_lowrank_batch,
)
from repro.obs.dispatch import record_chunk, record_greedy_map

_BACKENDS = ("auto", "jnp", "pallas", "sharded")


class GreedySpecError(ValueError):
    """Invalid ``GreedySpec`` — raised at spec construction time."""


@dataclasses.dataclass(frozen=True)
class GreedySpec:
    """How to run greedy MAP: slate size, window, backend, mesh, tolerance."""

    k: int
    window: Optional[int] = None  # None = exact Algorithm 1
    backend: str = "auto"  # "auto" | "jnp" | "pallas" | "sharded"
    eps: float = 1e-6
    mesh: Optional[object] = None  # jax Mesh for the sharded backend
    axis_name: str = "data"  # mesh axis the candidate axis shards over
    # Pallas candidate-axis tile: an explicit LANE multiple, or None
    # (worked out from the shapes)
    tile_m: Optional[int] = None
    chunk_size: Optional[int] = None  # greedy steps per resumable chunk

    def __post_init__(self):
        if self.k <= 0:
            raise GreedySpecError(f"k must be >= 1, got {self.k}")
        if self.window is not None and self.window < 1:
            raise GreedySpecError(f"window must be >= 1, got {self.window}")
        if self.chunk_size is not None:
            if self.chunk_size < 1:
                raise GreedySpecError(
                    f"chunk_size must be >= 1, got {self.chunk_size}"
                )
            if self.backend == "jnp" or (
                self.backend == "auto" and self.mesh is None
            ):
                raise GreedySpecError(
                    "chunk_size= selects chunked execution, which only the "
                    "pallas (fused multi-step chunk kernels) and sharded "
                    "(device-resident chunk state) backends implement — on "
                    "the jnp whole-slate path it would be silently ignored; "
                    "stream through greedy_map_chunks(..., chunk_size=) "
                    "instead"
                )
        if self.tile_m is not None:
            from repro.kernels.dpp_greedy.tiling import validate_tile_m

            try:
                validate_tile_m(self.tile_m)
            except ValueError as e:
                raise GreedySpecError(str(e)) from None
            if self.backend == "jnp" or (
                self.backend == "auto" and self.mesh is None
            ):
                raise GreedySpecError(
                    "tile_m= only applies to the Pallas kernels "
                    "(backend='pallas', or 'sharded'/'auto' with a mesh) "
                    "— on the jnp backend it would be silently ignored"
                )
        if self.backend not in _BACKENDS:
            raise GreedySpecError(
                f"unknown backend {self.backend!r}; expected one of {_BACKENDS}"
            )
        if self.backend == "sharded" and self.mesh is None:
            raise GreedySpecError("backend='sharded' needs mesh= (and axis_name=)")
        if self.mesh is not None and self.backend not in ("auto", "sharded"):
            raise GreedySpecError(
                f"mesh= only applies to the sharded backend (backend='sharded' "
                f"or 'auto'), not {self.backend!r} — a mesh with a "
                f"single-device backend would be silently ignored"
            )

    def windowed(self) -> bool:
        return self.window is not None and self.window < self.k

    def sharded(self) -> bool:
        return self.backend == "sharded" or (
            self.backend == "auto" and self.mesh is not None
        )


def greedy_map(
    spec: GreedySpec,
    *,
    L: Optional[jnp.ndarray] = None,
    V: Optional[jnp.ndarray] = None,
    mask: Optional[jnp.ndarray] = None,
) -> GreedyResult:
    """Run greedy DPP MAP per ``spec`` on a dense (L) or low-rank (V) kernel.

    Accepts single problems (L (M, M) / V (D, M)) and user batches
    (L (B, M, M) / V (B, D, M)); returns a ``GreedyResult`` whose leaves
    gain a leading batch dimension in the batched case.  The sharded
    backend is low-rank only; batched inputs keep the candidate axis
    sharded and run all B users on the mesh at once.

    ``mask`` may be per-problem ((M,) single / (B, M) batched) or — a
    shared candidate filter applied to every user of a batch — a single
    (M,) vector alongside a batched L/V; it is broadcast to (B, M)
    before dispatch so every backend sees the same per-user shape.
    """
    if (L is None) == (V is None):
        raise ValueError("pass exactly one of L= (dense) or V= (low-rank)")
    if spec.backend == "pallas" and L is not None:
        raise ValueError(
            "backend='pallas' needs the low-rank V — the kernel never "
            "materializes the dense L"
        )

    kern = L if L is not None else V
    if mask is not None and kern.ndim == 3 and mask.ndim == 1:
        # shared (M,) mask with a batched kernel: every backend's batch
        # path consumes a (B, M) mask (the jnp paths vmap over it, the
        # pallas kernel reshapes to (B, 1, M)), so broadcast here once
        mask = jnp.broadcast_to(mask, (kern.shape[0], mask.shape[0]))

    # static shapes only — trace-safe; chunked runs count their launched
    # steps per chunk (greedy_chunk), unchunked ones here
    record_greedy_map(
        "sharded" if spec.sharded()
        else "pallas" if spec.backend == "pallas" else "jnp",
        B=kern.shape[0] if kern.ndim == 3 else 1,
        k=spec.k,
        M=kern.shape[-1],
        chunked=spec.chunk_size is not None,
    )

    if spec.chunk_size is not None:
        # chunked whole-slate execution (pallas: fused multi-step chunk
        # kernels; sharded: device-resident chunk state) — identical
        # slate to the unchunked paths, validated by tests/test_streaming
        chunks = list(greedy_map_chunks(spec, L=L, V=V, mask=mask))
        sel = jnp.concatenate([c.indices for c in chunks], axis=-1)
        dh = jnp.concatenate([c.d_hist for c in chunks], axis=-1)
        n = jnp.sum(sel >= 0, axis=-1).astype(jnp.int32)
        return GreedyResult(sel, n, dh)

    if spec.sharded():
        if L is not None:
            raise ValueError(
                "backend='sharded' needs the low-rank V — a dense L cannot "
                "be candidate-sharded"
            )
        from repro.core.sharded import dpp_greedy_sharded

        return dpp_greedy_sharded(
            V,
            spec.k,
            mesh=spec.mesh,
            axis_name=spec.axis_name,
            window=spec.window,
            eps=spec.eps,
            mask=mask,
            tile_m=spec.tile_m,
        )

    if spec.backend == "pallas":
        from repro.kernels.dpp_greedy import dpp_greedy as dpp_greedy_pallas

        batched = V.ndim == 3
        Vb = V if batched else V[None]
        mb = mask if (mask is None or batched) else mask[None]
        sel, dh = dpp_greedy_pallas(
            Vb,
            spec.k,
            mask=mb,
            eps=spec.eps,
            window=spec.window,
            tile_m=spec.tile_m,
        )
        n = jnp.sum(sel >= 0, axis=-1).astype(jnp.int32)
        res = GreedyResult(sel, n, dh)
        if batched:
            return res
        return GreedyResult(sel[0], n[0], dh[0])

    if L is not None:
        batched = L.ndim == 3
        if spec.windowed():
            fn = dpp_greedy_windowed_batch if batched else dpp_greedy_windowed
            return fn(L, spec.k, spec.window, spec.eps, mask)
        fn = dpp_greedy_dense_batch if batched else dpp_greedy_dense
        return fn(L, spec.k, spec.eps, mask)

    batched = V.ndim == 3
    if spec.windowed():
        fn = (
            dpp_greedy_windowed_lowrank_batch
            if batched
            else dpp_greedy_windowed_lowrank
        )
        return fn(V, spec.k, spec.window, spec.eps, mask)
    fn = dpp_greedy_lowrank_batch if batched else dpp_greedy_lowrank
    return fn(V, spec.k, spec.eps, mask)


def record_greedy_call(spec: GreedySpec, *, B: int, D: int, M: int) -> None:
    """Record on the host what ``greedy_map(spec, V=(B, D, M))`` records
    for one call: the backend, and on pallas the kernel's mode and tile.

    For a caller that runs ``greedy_map`` inside a compiled program:
    the program's body runs its Python, hooks included, only while it
    is traced, so the caller mutes the hooks there (``obs.muted``) and
    calls this once per call instead.  The kernel is a function of the
    spec and the shapes, so the program needs no key of its own for it.
    Single-device backends only."""
    chunked = spec.chunk_size is not None
    pallas = spec.backend == "pallas"
    record_greedy_map("pallas" if pallas else "jnp", B=B, k=spec.k, M=M,
                      chunked=chunked)
    if not pallas:
        return
    from repro.kernels.dpp_greedy.ops import (
        dpp_greedy_plan,
        dpp_greedy_stream_plan,
    )

    if not chunked:
        dpp_greedy_plan(D, M, spec.k, spec.window, spec.tile_m)
        return
    R = min(spec.window, spec.k) if spec.windowed() else spec.k
    _, Mp = dpp_greedy_stream_plan(D, M, R, spec.windowed(), spec.tile_m)
    for done in range(0, spec.k, spec.chunk_size):
        record_chunk("pallas", B=B, chunk=min(spec.chunk_size, spec.k - done),
                     M=Mp)


def greedy_map_chunks(
    spec: GreedySpec,
    *,
    L: Optional[jnp.ndarray] = None,
    V: Optional[jnp.ndarray] = None,
    mask: Optional[jnp.ndarray] = None,
    chunk_size: Optional[int] = None,
):
    """Generator running greedy MAP per ``spec`` in resumable chunks.

    Yields ``ceil(k / chunk)`` :class:`GreedyResult`s whose ``indices``
    / ``d_hist`` cover ``chunk`` selections each (the last chunk is
    short when ``chunk`` does not divide ``k``); their concatenation is
    exactly the whole-slate ``greedy_map`` result — indices
    index-for-index, ``d_hist`` bitwise on jnp and to ~1 ulp across
    kernels.  After an eps-stop the remaining slots hold -1 / 0, as the
    whole-slate tail does.

    ``chunk_size`` overrides ``spec.chunk_size`` — that is how the jnp
    backend (whose spec cannot carry a chunk size, see ``GreedySpec``)
    streams.  Backends: jnp takes single problems (dense L or low-rank
    V); pallas and sharded take single or batched low-rank V.
    """
    from repro.core.streaming import greedy_chunk, greedy_init, resolve_chunk

    chunk = resolve_chunk(spec, chunk_size)
    kern = L if L is not None else V
    if mask is not None and kern is not None and kern.ndim == 3 \
            and mask.ndim == 1:
        mask = jnp.broadcast_to(mask, (kern.shape[0], mask.shape[0]))
    state = greedy_init(spec, L=L, V=V, mask=mask)
    # pad/cast the kernel operand to the state's padded geometry ONCE —
    # the chunk executors skip their copy when the shape already
    # matches, so the loop below moves no O(D M) data per chunk
    if spec.sharded():
        from repro.core.sharded import _stream_pad

        V = _stream_pad(V, state.d2.shape[-1])
    elif spec.backend == "pallas":
        from repro.kernels.dpp_greedy import dpp_greedy_stream_pad

        V = dpp_greedy_stream_pad(V, state)
    done = 0
    while done < spec.k:
        c = min(chunk, spec.k - done)
        state, sel, dh = greedy_chunk(spec, state, L=L, V=V, chunk_size=c)
        n = jnp.sum(sel >= 0, axis=-1).astype(jnp.int32)
        yield GreedyResult(sel, n, dh)
        done += c
