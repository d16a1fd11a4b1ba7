"""The tile of the dpp_greedy Pallas kernels — one decision, made from
the shapes by a VMEM *model*, or pinned by an explicit int.

Earlier revisions guarded the kernel with a single whole-array check
(``vmem_bytes(D, M, state_rows) > VMEM_BUDGET_BYTES`` -> silently fall
back to pure jnp), which surrendered exactly the large-M regime the
paper's O(M)-per-step update is about.  :func:`plan_tile` replaces that
gate with a decision between two *kernel* execution modes:

* **resident** — the whole working set (``V (D, M)``, the Cholesky
  state ``C (state_rows, M)`` and a few ``(1, M)`` rows) fits in VMEM:
  run the classic whole-slate kernels in ``dpp_greedy.py`` (the entire
  greedy loop inside one ``pallas_call``, zero HBM round-trips between
  steps).
* **tiled** — the working set exceeds the budget: run the streaming
  kernels in ``tiled.py``.  Each greedy step is one grid sweep over
  ``M``-tiles; per grid step only ``(D, tile_m)`` of ``V`` and
  ``(state_rows, tile_m)`` of ``C`` are VMEM-resident, and the Pallas
  BlockSpec pipeline double-buffers the HBM->VMEM (and VMEM->HBM)
  copies of consecutive tiles.  The VMEM bound is per *tile*, so M is
  unbounded.

The pure-jnp path survives only as an explicit escape hatch
(``force_jnp=True``) and as a last resort when even a single
lane-width tile would not fit (pathological ``D``/``state_rows``).

The resident-mode working set is :func:`untiled_vmem_bytes`, the
per-tile model :func:`tile_vmem_bytes`.
"""
from __future__ import annotations

from typing import Optional

LANE = 128
SUBLANE = 8
# Budget for the f32 working set the models below count (operand blocks,
# double-buffered where the pipeline streams them, plus scratch).
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
# Scoped-VMEM limit every dpp_greedy pallas_call compiles with.  Under
# Mosaic's default (16 MiB on v5e) the compiler refuses the resident
# kernels at the budget's edge: the pipeline double-buffers the whole
# V block and the kernels hold full-width temporaries (one-hot masks,
# products) beside it.  TPU v5e has 128 MiB of VMEM per core; every
# geometry plan_tile can pick compiles under this limit
# (tests/test_tpu_compile.py).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# Upper bound for auto-chosen tiles: past this, wider tiles stop paying
# (DMA is already fully amortized) and only lengthen the pipeline warmup.
MAX_AUTO_TILE = 1 << 16


def round_up(x: int, m: int) -> int:
    """Smallest multiple of ``m`` >= ``x`` (TPU lane/sublane alignment)."""
    return (x + m - 1) // m * m


def validate_tile_m(tile_m: Optional[int]) -> None:
    """Shared tile_m validation (every call site that accepts a tile:
    ``GreedySpec``, ``DPPRerankConfig``, ``dpp_greedy``,
    ``dpp_greedy_sharded``): ``None`` (the shapes decide) or a positive
    LANE multiple."""
    if tile_m is None:
        return
    if (not isinstance(tile_m, int) or isinstance(tile_m, bool)
            or tile_m < LANE or tile_m % LANE != 0):
        raise ValueError(
            f"tile_m must be None or a positive multiple of the "
            f"{LANE}-lane register width, got {tile_m!r}"
        )


def untiled_vmem_bytes(D: int, M: int, state_rows: int) -> int:
    """Whole-array (resident-mode) VMEM working set.

    ``V`` (D, M) + ``C`` (state_rows, M) + a few (1, M) rows, all f32,
    padded to the (SUBLANE, LANE) f32 tile.  ``state_rows`` is ``k``
    (full slate) or ``w`` (windowed).
    """
    Mp, Dp = round_up(M, LANE), round_up(D, SUBLANE)
    return 4 * (Dp * Mp + round_up(state_rows, SUBLANE) * Mp + 8 * Mp)


def tile_vmem_bytes(
    D: int, tile_m: int, state_rows: int, windowed: bool = False,
    chunked: bool = False,
) -> int:
    """Per-grid-step VMEM working set of the tiled streaming kernels.

    Counts the double-buffered streams (x2: while tile ``i`` computes,
    the pipeline prefetches tile ``i+1`` and drains tile ``i-1``):
    the ``V`` tile (D, tile_m), the Cholesky tile in (state_rows,
    tile_m), the written-back tile and the d2 tile in/out; plus the
    small per-step replicated state (winner column, rotation
    coefficients, reduction cells), which does not scale with
    ``tile_m``.

    The written-back tile is a single appended row for the per-step
    exact sweep, but the **full** (state_rows, tile_m) state when
    ``windowed`` (post-eviction rewrite) *or* ``chunked`` (the fused
    multi-step chunk kernels stream the whole Cholesky block back out
    every step — see ``fused_chunk_exact``'s first out_spec).  The
    ``repro.analysis`` pallas-vmem-model rule cross-checks this count
    against the BlockSpecs the kernels actually declare.
    """
    Dp = round_up(D, SUBLANE)
    Rp = round_up(state_rows, SUBLANE)
    out_rows = Rp if (windowed or chunked) else SUBLANE
    streamed = Dp + Rp + out_rows + 2 * SUBLANE
    small = 4 * (Dp + Rp + 4 * LANE)
    return 4 * 2 * streamed * tile_m + small


def auto_tile(
    D: int, state_rows: int, windowed: bool, chunked: bool = False,
) -> int:
    """Widest LANE-multiple tile whose working set fits the budget
    (0 when even one lane-width tile does not fit)."""
    lo = tile_vmem_bytes(D, LANE, state_rows, windowed, chunked)
    if lo > VMEM_BUDGET_BYTES:
        return 0
    per_lane = tile_vmem_bytes(D, 2 * LANE, state_rows, windowed, chunked) - lo
    tm = LANE * (1 + (VMEM_BUDGET_BYTES - lo) // max(per_lane, 1))
    return min(tm, MAX_AUTO_TILE)


def plan_tile(
    D: int, M: int, state_rows: int, windowed: bool,
    chunked: bool = False, tile_m: Optional[int] = None,
) -> tuple[str, Optional[int]]:
    """-> ("resident", None) | ("tiled", tile_m) | ("jnp", None).

    An explicit ``tile_m`` always runs tiled at that width (that is how
    tiled-vs-resident parity is tested).  Unset: resident when the whole
    working set fits ``VMEM_BUDGET_BYTES``, else the widest fitting tile
    (:func:`auto_tile`, capped at ``round_up(M, LANE)``), else jnp.

    ``chunked`` must be set when the tile will feed the fused
    multi-step chunk kernels, whose per-tile working set is larger
    than the per-step exact sweep's (full state streams back out
    every step) — sizing a chunked tile with the per-step model
    overflows the budget by ``~8 * state_rows * tile_m`` bytes.
    """
    validate_tile_m(tile_m)
    if tile_m is not None:
        return "tiled", tile_m
    if untiled_vmem_bytes(D, M, state_rows) <= VMEM_BUDGET_BYTES:
        return "resident", None
    tm = auto_tile(D, state_rows, windowed, chunked)
    if tm == 0:
        return "jnp", None
    return "tiled", min(tm, round_up(M, LANE))
