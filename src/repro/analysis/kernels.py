"""Pallas kernel contract checker (rules pallas-coverage-gap,
pallas-block-divisibility, pallas-revisit-gap, pallas-vmem-budget,
pallas-vmem-model).

This checker is *static analysis by abstract execution*: it never runs
a kernel body.  ``pl.pallas_call`` is temporarily replaced with a
recorder that captures ``(grid, BlockSpecs, operand shapes)`` and
returns zeros, then the tiled seams (``_full_sweep`` /
``_windowed_sweep`` / the fused chunk wrappers, via ``__wrapped__`` to
bypass jit) are driven over representative ``(D, state_rows, windowed,
chunked)`` geometries.  Each recorded launch's ``index_map``s are then
evaluated over the full grid product — plain Python ints in, block
indices out — which makes every property below decidable exactly:

* **coverage** — the union of visited block indices equals the full
  block grid of every operand (nothing is silently never read or
  written);
* **divisibility** — every block shape divides its (padded) operand
  dimension;
* **revisit contiguity** — an output block revisited at
  *non-consecutive* grid steps (the fused chunk kernels' cross-step
  C/d2 state when ``nt > 1``) is only legal behind the interpret-mode
  guard: the checker re-drives the seam with ``interpret=False`` and
  requires ``NotImplementedError`` (ROADMAP's Mosaic hazard, made
  unreachable rather than latent);
* **VMEM model faithfulness** — ``tiling.tile_vmem_bytes``'s per-lane
  slope must cover the streamed rows the BlockSpecs actually declare
  (an undercount makes ``tiling.auto_tile`` pick overflowing tiles);
* **VMEM budget** — for every geometry ``tiling.plan_tile`` can choose,
  the planned tile's working set (model *and* recorded-spec actuals)
  fits ``VMEM_BUDGET_BYTES``, and the non-streamed replicated cells
  stay bounded.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Callable, Optional

from repro.analysis.findings import Finding

LANE = 128
SUBLANE = 8
_BIG_M = 1 << 22  # forces plan_tile() off the resident path
_CELL_BYTES_BOUND = 1 << 20  # replicated cells must not scale


@dataclasses.dataclass
class RecordedCall:
    """One captured ``pallas_call`` launch."""

    name: str
    grid: tuple[int, ...]
    in_specs: tuple
    out_specs: tuple
    in_shapes: tuple[tuple[int, ...], ...]
    out_shapes: tuple[tuple[int, ...], ...]
    interpret: bool


@dataclasses.dataclass
class DrivenSeam:
    """A recorded launch plus the geometry/meta it was driven with."""

    call: RecordedCall
    family: str
    D: int
    state_rows: int
    windowed: bool
    chunked: bool
    path: str
    line: int
    # re-drives the same geometry compiled; must raise
    # NotImplementedError whenever the launch has revisit gaps
    compiled_probe: Optional[Callable[[], None]] = None


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _kernel_name(kernel) -> str:
    fn = getattr(kernel, "func", kernel)
    return getattr(fn, "__name__", repr(fn))


class _Recorder:
    """Stand-in for ``pl.pallas_call``: records the launch geometry and
    returns zeros without executing the kernel."""

    def __init__(self):
        self.calls: list[RecordedCall] = []

    def __call__(self, kernel, *, grid, in_specs, out_specs, out_shape,
                 interpret=False, **_kw):
        import jax.numpy as jnp

        def run(*ins):
            self.calls.append(RecordedCall(
                name=_kernel_name(kernel),
                grid=tuple(grid),
                in_specs=tuple(in_specs),
                out_specs=tuple(out_specs),
                in_shapes=tuple(tuple(x.shape) for x in ins),
                out_shapes=tuple(tuple(s.shape) for s in out_shape),
                interpret=bool(interpret),
            ))
            return [jnp.zeros(s.shape, s.dtype) for s in out_shape]

        return run


# --------------------------------------------------------------------------
# Drivers
# --------------------------------------------------------------------------

# the geometries plan_tile can be asked to tile: feature dims across
# the sublane boundary, state rows from tiny windows to full slates
SWEEP_D = (8, 64, 256)
SWEEP_R = (8, 48, 128)
_DRIVE_TILE = LANE  # smallest legal tile; streamed rows are
_DRIVE_NT = 2  # tile-width-independent, and nt=2 exposes revisit gaps


def _drive_family(tiled, family: str, D: int, R: int,
                  recorder: _Recorder) -> DrivenSeam:
    import inspect
    import os

    import jax.numpy as jnp

    B, tile = 1, _DRIVE_TILE
    Mp = _DRIVE_NT * tile
    V = jnp.zeros((B, D, Mp), jnp.float32)
    C = jnp.zeros((B, R, Mp), jnp.float32)
    stopped = jnp.zeros((B,), bool)
    windowed = family.endswith("windowed")
    chunked = family.startswith("chunk")
    probe = None

    if family == "step_exact":
        target = tiled._full_sweep
        run = lambda: tiled._full_sweep(  # noqa: E731
            V, C, jnp.zeros((B, 1, Mp), jnp.float32),
            jnp.zeros((B, D, 1), jnp.float32),
            jnp.zeros((B, R, 1), jnp.float32),
            jnp.zeros((B, 1, 2), jnp.float32),
            jnp.zeros((B, 1, 2), jnp.int32),
            tile_m=tile, interpret=True,
        )
    elif family == "step_windowed":
        target = tiled._windowed_sweep
        nf = 3 + 2 * (R - 1)
        run = lambda: tiled._windowed_sweep(  # noqa: E731
            V, C, jnp.zeros((B, 1, Mp), jnp.float32),
            jnp.zeros((B, D, 1), jnp.float32),
            jnp.zeros((B, R, 1), jnp.float32),
            jnp.zeros((B, 1, nf), jnp.float32),
            jnp.zeros((B, 1, 3), jnp.int32),
            w=R, tile_m=tile, interpret=True,
        )
    elif family == "chunk_exact":
        target = tiled.fused_chunk_exact.__wrapped__
        d2 = jnp.zeros((B, Mp), jnp.float32)
        run = lambda: target(  # noqa: E731
            V, C, d2, 0, stopped, chunk=2, eps=1e-3, tile_m=tile,
            interpret=True,
        )
        probe = lambda: target(  # noqa: E731
            V, C, d2, 0, stopped, chunk=2, eps=1e-3, tile_m=tile,
            interpret=False,
        )
    elif family == "chunk_windowed":
        target = tiled.fused_chunk_windowed.__wrapped__
        d2 = jnp.zeros((B, Mp), jnp.float32)
        win = jnp.full((B, R), -1, jnp.int32)
        run = lambda: target(  # noqa: E731
            V, C, d2, win, 0, stopped, chunk=2, eps=1e-3, w=R,
            tile_m=tile, interpret=True,
        )
        probe = lambda: target(  # noqa: E731
            V, C, d2, win, 0, stopped, chunk=2, eps=1e-3, w=R,
            tile_m=tile, interpret=False,
        )
    else:  # pragma: no cover - driver misuse
        raise ValueError(f"unknown family {family!r}")

    before = len(recorder.calls)
    run()
    if len(recorder.calls) != before + 1:  # pragma: no cover
        raise RuntimeError(
            f"driving {family} recorded {len(recorder.calls) - before} "
            f"pallas_call launches, expected exactly 1"
        )
    path = os.path.relpath(inspect.getsourcefile(tiled))
    line = target.__code__.co_firstlineno
    return DrivenSeam(
        call=recorder.calls[-1], family=family, D=D, state_rows=R,
        windowed=windowed, chunked=chunked, path=path, line=line,
        compiled_probe=probe,
    )


def harvest_seams() -> list[DrivenSeam]:
    """Drive every kernel family over the sweep geometries with the
    recorder patched in."""
    from repro.kernels.dpp_greedy import tiled

    recorder = _Recorder()
    seams: list[DrivenSeam] = []
    orig = tiled.pl.pallas_call
    tiled.pl.pallas_call = recorder
    try:
        for family in ("step_exact", "step_windowed", "chunk_exact",
                       "chunk_windowed"):
            for D, R in itertools.product(SWEEP_D, SWEEP_R):
                seams.append(_drive_family(tiled, family, D, R, recorder))
    finally:
        tiled.pl.pallas_call = orig
    return seams


# --------------------------------------------------------------------------
# Abstract index_map evaluation
# --------------------------------------------------------------------------


def _norm_block(spec) -> tuple[int, ...]:
    return tuple(1 if b is None else int(b) for b in spec.block_shape)


def _index_seq(spec, grid) -> list[tuple[int, ...]]:
    return [tuple(int(i) for i in spec.index_map(*pt))
            for pt in itertools.product(*(range(g) for g in grid))]


def _is_streamed(spec, grid) -> bool:
    """Does the block index vary along the tile (last grid) axis?"""
    base = tuple(0 for _ in grid)
    alt = base[:-1] + (1,)
    return (tuple(spec.index_map(*base))
            != tuple(spec.index_map(*alt)))


def _revisit_gaps(seq: list[tuple[int, ...]]) -> list[tuple[int, ...]]:
    last: dict[tuple[int, ...], int] = {}
    gapped = []
    for pos, ib in enumerate(seq):
        prev = last.get(ib)
        if prev is not None and pos - prev > 1:
            gapped.append(ib)
        last[ib] = pos
    return sorted(set(gapped))


def check_launch_geometry(seam: DrivenSeam) -> list[Finding]:
    """Coverage, divisibility and revisit-contiguity for one recorded
    launch (pure combinatorics over the captured BlockSpecs)."""
    findings: list[Finding] = []
    rec = seam.call
    operands = (
        [("in", i, s, sh) for i, (s, sh) in
         enumerate(zip(rec.in_specs, rec.in_shapes))]
        + [("out", i, s, sh) for i, (s, sh) in
           enumerate(zip(rec.out_specs, rec.out_shapes))]
    )
    gapped_outputs = []
    for role, idx, spec, shape in operands:
        block = _norm_block(spec)
        if len(block) != len(shape):  # pragma: no cover - malformed spec
            findings.append(Finding(
                seam.path, seam.line, "pallas-coverage-gap",
                f"{rec.name} {role}[{idx}]: block rank {len(block)} vs "
                f"operand rank {len(shape)}",
            ))
            continue
        for d, (dim, b) in enumerate(zip(shape, block)):
            if dim % b != 0:
                findings.append(Finding(
                    seam.path, seam.line, "pallas-block-divisibility",
                    f"{rec.name} {role}[{idx}] dim {d}: block {b} does "
                    f"not divide padded extent {dim} "
                    f"(family={seam.family}, D={seam.D}, "
                    f"R={seam.state_rows})",
                ))
        nblocks = tuple(-(-dim // b) for dim, b in zip(shape, block))
        seq = _index_seq(spec, rec.grid)
        visited = set(seq)
        full = set(itertools.product(*(range(n) for n in nblocks)))
        stray = sorted(visited - full)
        missing = sorted(full - visited)
        if stray:
            findings.append(Finding(
                seam.path, seam.line, "pallas-coverage-gap",
                f"{rec.name} {role}[{idx}]: index_map leaves the block "
                f"grid {nblocks} at {stray[:4]} "
                f"(family={seam.family}, D={seam.D}, "
                f"R={seam.state_rows})",
            ))
        if missing:
            findings.append(Finding(
                seam.path, seam.line, "pallas-coverage-gap",
                f"{rec.name} {role}[{idx}]: blocks never visited over "
                f"the full grid {rec.grid}: {missing[:4]} "
                f"(family={seam.family}, D={seam.D}, "
                f"R={seam.state_rows})",
            ))
        if role == "out" and _revisit_gaps(seq):
            gapped_outputs.append(idx)

    if gapped_outputs:
        guarded = False
        if seam.compiled_probe is not None:
            try:
                seam.compiled_probe()
            except NotImplementedError:
                guarded = True
        if not guarded:
            findings.append(Finding(
                seam.path, seam.line, "pallas-revisit-gap",
                f"{rec.name} outputs {gapped_outputs} are revisited at "
                f"non-consecutive grid steps over grid {rec.grid} and "
                f"compiling is not guarded — compiled Mosaic does not "
                f"preserve a revisited output block across intervening "
                f"steps (family={seam.family})",
            ))
    return findings


# --------------------------------------------------------------------------
# VMEM model / budget
# --------------------------------------------------------------------------


def _stream_accounting(rec: RecordedCall) -> tuple[int, int]:
    """(streamed padded rows per tile, non-streamed cell bytes) from
    the recorded BlockSpecs — f32/i32, rank-3 blocks."""
    rows = 0
    cell_bytes = 0
    for spec, _shape in itertools.chain(
        zip(rec.in_specs, rec.in_shapes), zip(rec.out_specs, rec.out_shapes)
    ):
        block = _norm_block(spec)
        lead = 1
        for b in block[:-2]:
            lead *= b
        if _is_streamed(spec, rec.grid):
            rows += lead * _round_up(block[-2], SUBLANE)
        else:
            cell_bytes += (
                4 * lead * _round_up(block[-2], SUBLANE)
                * _round_up(block[-1], LANE)
            )
    return rows, cell_bytes


def check_vmem_contract(seam: DrivenSeam) -> list[Finding]:
    from repro.kernels.dpp_greedy.tiling import (
        VMEM_BUDGET_BYTES,
        plan_tile,
        tile_vmem_bytes,
    )

    findings: list[Finding] = []
    D, R = seam.D, seam.state_rows
    rows, cell_bytes = _stream_accounting(seam.call)
    model = functools.partial(
        tile_vmem_bytes, D, state_rows=R, windowed=seam.windowed,
        chunked=seam.chunked,
    )
    model_rows = (model(tile_m=2 * LANE) - model(tile_m=LANE)) // (8 * LANE)
    geom = (f"family={seam.family}, D={D}, R={R}, "
            f"windowed={seam.windowed}, chunked={seam.chunked}")
    if model_rows < rows:
        findings.append(Finding(
            seam.path, seam.line, "pallas-vmem-model",
            f"tile_vmem_bytes counts {model_rows} streamed rows/tile "
            f"but the recorded BlockSpecs stream {rows} ({geom}) — "
            f"auto_tile would pick an overflowing tile",
        ))

    mode, tm = plan_tile(D, _BIG_M, R, seam.windowed, chunked=seam.chunked)
    if mode == "tiled" and tm:
        if model(tile_m=tm) > VMEM_BUDGET_BYTES:
            findings.append(Finding(
                seam.path, seam.line, "pallas-vmem-budget",
                f"plan_tile picked tile_m={tm} whose own model "
                f"estimate {model(tile_m=tm)} exceeds the "
                f"{VMEM_BUDGET_BYTES}-byte budget ({geom})",
            ))
        actual_stream = 4 * 2 * rows * tm
        if actual_stream > VMEM_BUDGET_BYTES:
            findings.append(Finding(
                seam.path, seam.line, "pallas-vmem-budget",
                f"plan_tile picked tile_m={tm} but the recorded "
                f"BlockSpecs stream {actual_stream} double-buffered "
                f"bytes/tile, over the {VMEM_BUDGET_BYTES}-byte "
                f"budget ({geom})",
            ))
    if cell_bytes > _CELL_BYTES_BOUND:
        findings.append(Finding(
            seam.path, seam.line, "pallas-vmem-budget",
            f"replicated (non-streamed) cells occupy {cell_bytes} "
            f"bytes — they must stay within the model's fixed "
            f"headroom (< {_CELL_BYTES_BOUND}) ({geom})",
        ))
    return findings


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------


def check_kernel_contracts() -> tuple[list[Finding], dict]:
    """Drive, record, and verify every kernel family.  Returns
    (deduplicated findings, summary)."""
    seams = harvest_seams()
    findings: list[Finding] = []
    for seam in seams:
        findings.extend(check_launch_geometry(seam))
        findings.extend(check_vmem_contract(seam))
    seen = set()
    unique = []
    for f in findings:
        key = (f.rule, f.path, f.line)
        if key not in seen:
            seen.add(key)
            unique.append(f)
    summary = {
        "families": sorted({s.family for s in seams}),
        "geometries": len(seams),
        "launches_recorded": len(seams),
    }
    return unique, summary
