"""Tiled, double-buffered Pallas greedy kernels — past-the-VMEM-gate M.

The resident kernels in ``dpp_greedy.py`` hold ``V (D, M)`` and the
Cholesky state whole in VMEM, which caps M at the VMEM budget.  Here
each greedy step is one **grid sweep over M-tiles**: per grid step only
a ``(D, tile_m)`` block of ``V``, a ``(state_rows, tile_m)`` block of
``C`` and a ``(1, tile_m)`` block of ``d2`` are VMEM-resident, and the
Pallas BlockSpec pipeline double-buffers the HBM->VMEM / VMEM->HBM
copies of consecutive tiles while the current tile computes.

Per-step structure (the paper's eqs. 13/16-18 restructured for
streaming):

1. **streamed pass** (``_pass_full`` / ``_pass_windowed``): every tile
   applies the update for the *previously selected* winner ``j`` —
   ``e = (L_j - c_j^T C) / d_j``, ``d2 -= e^2``, the row append (and,
   windowed, the eviction Givens rotations) — and folds a running
   ``(d2_max, argmax)`` reduction into revisited ``(1, 1)`` output
   cells, so the next winner is known when the sweep ends;
2. **winner-column visit**: only the winner's column is touched —
   ``V[:, j]`` and ``C[:, j]`` are gathered at the JAX level (an O(D)
   /O(state_rows) dynamic slice into HBM, not another sweep) and fed
   to the next step's pass as tiny replicated ``(rows, 1)`` columns.

Everything data-dependent but small — the winner column, the windowed
eviction rotation coefficients (computed from the ``(w, w)`` window
factor ``C[:, win]``), the eps-stop flag — is resolved between sweeps
at the JAX level, so the kernels themselves stay shape-static.

Kernel bodies are written for the Mosaic TPU compiler, which has no
lane-axis dynamic slice and no scalar stores to VMEM: a "scalar" inside
a kernel is a ``(1, 1)`` vector, a data-dependent column or lane is
read with a one-hot masked reduction (:func:`_lane_pick`), and a
data-dependent row or lane is written with a one-hot select
(:func:`_row_set` / :func:`_lane_set`).  The matvecs ``v_j^T V`` and
``c_j^T C`` are broadcast-multiply-reduce on the VPU in f32 — exact
f32 products, no MXU precision mode to pick — and every kernel family
calls the same per-tile update functions, so the resident, per-step
and fused chunk kernels compute identical bits.

The same pass kernels serve the candidate-sharded backend: each device
of ``repro.core.sharded`` runs the identical local update on its
``(D, M/P)`` shard (``tiled_update_exact`` / ``tiled_update_windowed``
with the shard's global column offset), so sharded M/P blocks scale
past the VMEM budget exactly like the single-device path.

Dispatch between resident and tiled kernels lives in ``ops.py`` via
``repro.kernels.dpp_greedy.tiling.plan_tile``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dpp_greedy.tiling import VMEM_LIMIT_BYTES
from repro.kernels.platform import resolve_interpret

NEG_INF = float("-inf")


# Mosaic parameters shared by every dpp_greedy pallas_call: the
# scoped-VMEM limit the plan_tile budget was checked against.
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


# ---------------------------------------------------------------------------
# In-kernel vector helpers (Mosaic-lowerable forms of gather/scatter)
# ---------------------------------------------------------------------------


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _index(idx):
    """A ``(1, 1)`` index vector as a scalar (a full reduction, whose
    result Mosaic can broadcast into any one-hot compare; ints and
    scalars pass through)."""
    return jnp.max(idx) if getattr(idx, "ndim", 0) == 2 else idx


def _flag(b):
    """A ``(1, 1)`` bool as a scalar bool, for selects over 2-D blocks."""
    return jnp.max(b.astype(jnp.int32)) > 0


def _lane_pick(x, idx):
    """Column ``idx`` of ``x (R, n)`` as ``(R, 1)``: a one-hot masked
    lane-sum, exact (every other term is zero).  ``idx`` may be a static
    int, a traced scalar or a ``(1, 1)`` vector; an index outside
    ``[0, n)`` picks zeros."""
    hit = _iota(x.shape, 1) == _index(idx)
    return jnp.sum(jnp.where(hit, x, jnp.zeros_like(x)), axis=1, keepdims=True)


def _row_pick(x, idx):
    """Row ``idx`` of ``x (R, n)`` as ``(1, n)`` (sublane one-hot sum)."""
    hit = _iota(x.shape, 0) == _index(idx)
    return jnp.sum(jnp.where(hit, x, jnp.zeros_like(x)), axis=0, keepdims=True)


def _cell(ref):
    """A ``(1, 1)`` cell read through a lane reduction: a raw ``(1, 1)``
    load keeps a memory layout Mosaic cannot broadcast over both
    sublanes and lanes, a reduction result it can."""
    return _lane_pick(ref[...], 0)


def _lane_set(x, idx, v):
    """``x`` with column ``idx`` replaced by ``v`` (broadcast)."""
    return jnp.where(_iota(x.shape, 1) == _index(idx), v, x)


def _row_set(x, idx, v):
    """``x`` with row ``idx`` replaced by ``v`` (broadcast)."""
    return jnp.where(_iota(x.shape, 0) == _index(idx), v, x)


def _lane_row(vals, n):
    """Pack ``(1, 1)`` values into one ``(1, n)`` row, value ``i`` at
    lane ``i`` (lanes past ``len(vals)`` are zero)."""
    dtype = jnp.result_type(*vals)
    row = jnp.zeros((1, n), dtype)
    for i, v in enumerate(vals):
        row = _lane_set(row, i, jnp.asarray(v, dtype))
    return row


def _argmax_first(d2):
    """``(max (1, 1), argmax (1, 1) int32)`` of ``d2 (1, n)`` with
    ``jnp.argmax``'s first-occurrence tie-breaking (an all ``-inf`` row
    gives index 0)."""
    mx = jnp.max(d2, axis=1, keepdims=True)
    lanes = _iota(d2.shape, 1)
    am = jnp.min(
        jnp.where(d2 == mx, lanes, d2.shape[1]), axis=1, keepdims=True
    )
    return mx, am


# ---------------------------------------------------------------------------
# Per-tile update math (shared by every kernel family)
# ---------------------------------------------------------------------------


def _tile_update_full(V, C, d2, vj, cj, dj, stopped, j, base, i, tile_m):
    """The exact-step math for one (D, TM) tile, on plain values.

    ``vj (D, 1)`` / ``cj (R, 1)`` are the winner's columns; ``dj``,
    ``stopped``, ``j`` and ``base`` are ``(1, 1)`` (or scalars).  Shared
    by the resident, per-step and fused chunk kernels so all three run
    the identical op sequence.  Returns ``(e, d2o)``.
    """
    lj = jnp.sum(vj * V, axis=0, keepdims=True)
    dots = jnp.sum(cj * C, axis=0, keepdims=True)
    e = (lj - dots) / dj
    e = jnp.where(stopped, jnp.zeros_like(e), e)
    gid = _iota((1, tile_m), 1) + i * tile_m + _index(base)
    d2_next = jnp.where(gid == _index(j), NEG_INF, d2 - e * e)
    d2o = jnp.where(stopped, d2, d2_next)
    return e, d2o


def _tile_update_windowed(
    V, C, d2, vj, cj_post, djp, stopped, full, coss, sins, j, base, pos,
    i, w, tile_m,
):
    """The windowed-step math (evict + append fused) for one tile, on
    plain values.  ``coss``/``sins`` are length-(w-1) sequences of
    ``(1, 1)`` Givens coefficients, ``cj_post (w, 1)`` the winner's
    post-eviction column.  Returns ``(C_out, d2o, e)`` with ``C_out``
    already holding the stopped-passthrough."""
    # ---- evict the oldest pick: first-row Cholesky downdate; the
    # rotation residue u repairs d2 (see repro.core.windowed)
    zero = jnp.zeros((1, tile_m), jnp.float32)
    u = jnp.where(full, C[0:1, :], zero)
    Cpost = jnp.zeros((w, tile_m), jnp.float32)
    for r in range(w - 1):
        row = jnp.where(full, C[r + 1 : r + 2, :], C[r : r + 1, :])
        Cpost = _row_set(Cpost, r, coss[r] * row + sins[r] * u)
        u = coss[r] * u - sins[r] * row
    Cpost = _row_set(Cpost, w - 1, jnp.where(full, zero, C[w - 1 : w, :]))
    d2e = jnp.where(full, d2 + u * u, d2)

    # ---- append j against the post-eviction window (eqs. 16-18)
    lj = jnp.sum(vj * V, axis=0, keepdims=True)
    dots = jnp.sum(cj_post * Cpost, axis=0, keepdims=True)
    e = (lj - dots) / djp
    Cnew = _row_set(Cpost, pos, e)
    C_out = jnp.where(stopped, C, Cnew)

    gid = _iota((1, tile_m), 1) + i * tile_m + _index(base)
    d2_next = jnp.where(gid == _index(j), NEG_INF, d2e - e * e)
    d2o = jnp.where(stopped, d2, d2_next)
    return C_out, d2o, e


def _evict_coeffs_tile(Cw, cj, dj2, full, w):
    """In-kernel form of :func:`eviction_coeffs` on one problem.

    ``Cw (w, w)`` the window factor, ``cj (w, 1)`` the winner's
    pre-eviction column, ``dj2``/``full`` ``(1, 1)``.  Returns
    ``(coss, sins, cj_post (w, 1), d2j (1, 1))`` with ``coss``/``sins``
    lists of ``(1, 1)`` values — the identical recurrence, element for
    element."""
    tiny = 1e-30
    u_w = jnp.where(full, _row_pick(Cw, 0), jnp.zeros((1, w), jnp.float32))
    u_c = jnp.where(full, _row_pick(cj, 0), 0.0)
    coss, sins = [], []
    cpost = jnp.zeros((w, 1), jnp.float32)
    for r in range(w - 1):
        row_w = jnp.where(full, _row_pick(Cw, r + 1), _row_pick(Cw, r))
        row_c = jnp.where(full, _row_pick(cj, r + 1), _row_pick(cj, r))
        a = _lane_pick(row_w, r + 1)
        b = _lane_pick(u_w, r + 1)
        rho = jnp.maximum(jnp.sqrt(a * a + b * b), tiny)
        cos = jnp.where(full, a / rho, 1.0)
        sin = jnp.where(full, b / rho, 0.0)
        coss.append(cos)
        sins.append(sin)
        cpost = _row_set(cpost, r, cos * row_c + sin * u_c)
        u_c = cos * u_c - sin * row_c
        u_w = cos * u_w - sin * row_w
    cpost = _row_set(
        cpost, w - 1, jnp.where(full, 0.0, _row_pick(cj, w - 1))
    )
    d2j = jnp.where(full, dj2 + u_c * u_c, dj2)
    return coss, sins, cpost, d2j


# ---------------------------------------------------------------------------
# Per-tile pass kernels
# ---------------------------------------------------------------------------


def _reduce_running_argmax(i, d2, mx_ref, am_ref, tile_m):
    """Fold this tile's (max, argmax) of ``d2 (1, tile_m)`` into the
    revisited (1, 1) output cells; ties keep the earlier (lower) index,
    matching ``jnp.argmax`` over the concatenated axis."""

    @pl.when(i == 0)
    def _():
        mx_ref[...] = jnp.full(mx_ref.shape, NEG_INF, jnp.float32)
        am_ref[...] = jnp.zeros(am_ref.shape, jnp.int32)

    lm, la = _argmax_first(d2)
    cur = mx_ref[...]
    better = lm > cur
    mx_ref[...] = jnp.where(better, lm, cur)
    am_ref[...] = jnp.where(better, la + i * tile_m, am_ref[...])


def _pass_full(
    v_ref, c_ref, d2_ref, vj_ref, cj_ref, flt_ref, int_ref,
    e_ref, d2o_ref, mx_ref, am_ref, *, tile_m: int,
):
    """One M-tile of one exact-Algorithm-1 greedy step.

    v_ref:  (D, TM) f32 — tile of the scaled features, L = V^T V
    c_ref:  (R, TM) f32 — tile of the Cholesky rows (rows >= t are 0)
    d2_ref: (1, TM) f32 — tile of the marginal gains
    vj_ref: (D, 1), cj_ref: (R, 1) — the winner's columns (replicated)
    flt_ref:(1, 2) f32 — [d_j, stopped]
    int_ref:(1, 2) i32 — [j (global id), base (global id of column 0)]
    e_ref:  (1, TM) out — the appended Cholesky row (eqs. 16-18)
    d2o_ref:(1, TM) out — updated gains
    mx/am:  (1, 1) out — running (d2_max, argmax), revisited across tiles
    """
    i = pl.program_id(1)
    flt, ints = flt_ref[...], int_ref[...]
    e, d2o = _tile_update_full(
        v_ref[...], c_ref[...], d2_ref[...], vj_ref[...], cj_ref[...],
        _lane_pick(flt, 0), _lane_pick(flt, 1) > 0,
        _lane_pick(ints, 0), _lane_pick(ints, 1), i, tile_m,
    )
    e_ref[...] = e
    d2o_ref[...] = d2o
    _reduce_running_argmax(i, d2o, mx_ref, am_ref, tile_m)


def _pass_windowed(
    v_ref, c_ref, d2_ref, vj_ref, cj_ref, flt_ref, int_ref,
    co_ref, d2o_ref, mx_ref, am_ref, *, w: int, tile_m: int,
):
    """One M-tile of one sliding-window greedy step: eviction (Givens
    rotations with precomputed coefficients) fused with the append.

    c_ref:  (w, TM) — tile of the window Cholesky ring (window order)
    cj_ref: (w, 1)  — the winner's POST-eviction column (replicated)
    flt_ref:(1, 3 + 2(w-1)) f32 — [d_j', stopped, full,
            cos_0..cos_{w-2}, sin_0..sin_{w-2}]; identity rotations
            (cos=1, sin=0) are passed when the window is not yet full
    int_ref:(1, 3) i32 — [j, base, pos (ring row receiving the append)]
    co_ref: (w, TM) out — post-eviction, post-append ring tile
    """
    i = pl.program_id(1)
    flt, ints = flt_ref[...], int_ref[...]
    coss = [_lane_pick(flt, 3 + r) for r in range(w - 1)]
    sins = [_lane_pick(flt, 3 + (w - 1) + r) for r in range(w - 1)]
    C_out, d2o, _ = _tile_update_windowed(
        v_ref[...], c_ref[...], d2_ref[...], vj_ref[...], cj_ref[...],
        _lane_pick(flt, 0), _lane_pick(flt, 1) > 0, _lane_pick(flt, 2) > 0,
        coss, sins, _lane_pick(ints, 0), _lane_pick(ints, 1),
        _lane_pick(ints, 2), i, w, tile_m,
    )
    co_ref[...] = C_out
    d2o_ref[...] = d2o
    _reduce_running_argmax(i, d2o, mx_ref, am_ref, tile_m)


# ---------------------------------------------------------------------------
# pallas_call wrappers (one grid sweep = one greedy step)
# ---------------------------------------------------------------------------


def _tile_spec(rows, tile_m):
    return pl.BlockSpec((None, rows, tile_m), lambda b, i: (b, 0, i))


def _small_spec(rows, cols):
    return pl.BlockSpec((None, rows, cols), lambda b, i: (b, 0, 0))


def _sweep(kernel, name, row_out, V, C, d2, vj, cj, flt, ints, tile_m,
           interpret):
    """Run one per-step grid sweep.  ``row_out`` is the row count of the
    first (streamed) output: 1 for the exact append row, w for the
    windowed post-eviction ring.  ``name`` names the kernel family in
    the compiled program (``dpp_step_exact`` / ``dpp_step_windowed``)."""
    B, D, Mp = V.shape
    R = C.shape[1]
    nt = Mp // tile_m
    return pl.pallas_call(
        kernel,
        name=name,
        grid=(B, nt),
        in_specs=[
            _tile_spec(D, tile_m),
            _tile_spec(R, tile_m),
            _tile_spec(1, tile_m),
            _small_spec(D, 1),
            _small_spec(R, 1),
            _small_spec(1, flt.shape[-1]),
            _small_spec(1, ints.shape[-1]),
        ],
        out_specs=[
            _tile_spec(row_out, tile_m),
            _tile_spec(1, tile_m),
            _small_spec(1, 1),
            _small_spec(1, 1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, row_out, Mp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Mp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(V, C, d2, vj, cj, flt, ints)


def _full_sweep(V, C, d2, vj, cj, flt, ints, *, tile_m, interpret=None):
    kernel = functools.partial(_pass_full, tile_m=tile_m)
    return _sweep(kernel, "dpp_step_exact", 1, V, C, d2, vj, cj, flt, ints,
                  tile_m, interpret)


def _windowed_sweep(V, C, d2, vj, cj, flt, ints, *, w, tile_m,
                    interpret=None):
    kernel = functools.partial(_pass_windowed, w=w, tile_m=tile_m)
    return _sweep(kernel, "dpp_step_windowed", w, V, C, d2, vj, cj, flt,
                  ints, tile_m, interpret)


# ---------------------------------------------------------------------------
# Windowed eviction coefficients (shared with repro.core.sharded)
# ---------------------------------------------------------------------------


def eviction_coeffs(Cw, cj, dj2, full, w: int):
    """Precompute the first-row Cholesky-downdate rotations from the
    small replicated state, so a streamed sweep can apply them per tile.

    Cw:   (..., w, w) — the window factor C[:, win] (column s = window
          member s's Cholesky column); junk columns (win slot empty)
          must be zeroed by the caller.
    cj:   (..., w) — the winner's PRE-eviction Cholesky column.
    dj2:  (...,)   — the winner's selection-time marginal gain d_j^2.
    full: (...,) bool — eviction actually happens this step.

    Returns ``(cos (..., w-1), sin (..., w-1), cj_post (..., w),
    d2j (...,))`` — identity rotations, ``cj_post = cj`` and
    ``d2j = dj2`` wherever ``full`` is False.  Applying (cos, sin) to
    any column reproduces bit-for-bit what the in-place rotation sweep
    of ``repro.core.windowed`` / ``core.sharded`` computes, because the
    sweep only ever reads not-yet-rotated rows (row r+1 at iteration r).
    :func:`_evict_coeffs_tile` is the same recurrence inside a kernel.
    """
    tiny = 1e-30
    fullb = full[..., None]
    u_w = jnp.where(fullb, Cw[..., 0, :], 0.0)
    u_c = jnp.where(full, cj[..., 0], 0.0)
    coss, sins, cpost = [], [], []
    for r in range(w - 1):
        row_w = jnp.where(fullb, Cw[..., r + 1, :], Cw[..., r, :])
        row_c = jnp.where(full, cj[..., r + 1], cj[..., r])
        a = row_w[..., r + 1]
        b = u_w[..., r + 1]
        rho = jnp.maximum(jnp.sqrt(a * a + b * b), tiny)
        cos = jnp.where(full, a / rho, 1.0)
        sin = jnp.where(full, b / rho, 0.0)
        coss.append(cos)
        sins.append(sin)
        cpost.append(cos * row_c + sin * u_c)
        u_c = cos * u_c - sin * row_c
        u_w = cos[..., None] * u_w - sin[..., None] * row_w
    cpost.append(jnp.where(full, jnp.zeros_like(u_c), cj[..., w - 1]))
    shape = full.shape + (w - 1,)
    cos_arr = jnp.stack(coss, -1) if coss else jnp.zeros(shape, jnp.float32)
    sin_arr = jnp.stack(sins, -1) if sins else jnp.zeros(shape, jnp.float32)
    cj_post = jnp.stack(cpost, -1)
    d2j = jnp.where(full, dj2 + u_c * u_c, dj2)
    return cos_arr, sin_arr, cj_post, d2j


# ---------------------------------------------------------------------------
# Shard-local single-step updates (reused by repro.core.sharded)
# ---------------------------------------------------------------------------


def tiled_update_exact(
    Vl, C, d2, vj, cj, dj, stopped, j, base, *, tile_m: int,
    interpret=None,
):
    """One exact greedy step's local update on a column shard.

    Vl (D, Mloc) / C (k, Mloc) / d2 (Mloc,); vj (D,) / cj (k,) the
    winner's replicated columns; ``j`` the winner's *global* id and
    ``base`` this shard's global offset (0 on a single device).
    Returns ``(e (Mloc,), d2 (Mloc,))`` — the caller appends ``e`` as
    Cholesky row ``t``.  ``Mloc`` must be a multiple of ``tile_m``.
    """
    flt = jnp.stack([dj, stopped.astype(jnp.float32)])[None, None, :]
    ints = jnp.stack([j, base]).astype(jnp.int32)[None, None, :]
    e, d2o, _, _ = _full_sweep(
        Vl[None], C[None], d2[None, None, :], vj[None, :, None],
        cj[None, :, None], flt, ints, tile_m=tile_m, interpret=interpret,
    )
    return e[0, 0], d2o[0, 0]


def tiled_update_windowed(
    Vl, C, d2, vj, cj_post, djp, stopped, full, cos, sin, j, base, pos,
    *, w: int, tile_m: int, interpret=None,
):
    """One windowed greedy step's local update (evict + append fused) on
    a column shard; coefficients from :func:`eviction_coeffs`.
    Returns ``(C (w, Mloc), d2 (Mloc,))``."""
    flt = jnp.concatenate(
        [
            jnp.stack([djp, stopped.astype(jnp.float32),
                       full.astype(jnp.float32)]),
            cos, sin,
        ]
    )[None, None, :]
    ints = jnp.stack([j, base, pos]).astype(jnp.int32)[None, None, :]
    Co, d2o, _, _ = _windowed_sweep(
        Vl[None], C[None], d2[None, None, :], vj[None, :, None],
        cj_post[None, :, None], flt, ints, w=w, tile_m=tile_m,
        interpret=interpret,
    )
    return Co[0], d2o[0, 0]


# ---------------------------------------------------------------------------
# Fused multi-step chunk kernels (streaming emission / HBM amortization)
#
# One pallas_call advances ``chunk`` greedy steps: grid (B, chunk, nt),
# step-major, tile-minor.  The Cholesky state and d2 live in *output*
# blocks that sweep s+1 reads back (revisited block index maps ignore
# the step dimension), so C and d2 cross the kernel boundary — one HBM
# round-trip — once per chunk instead of once per step.  Everything the
# next step needs from the previous one (the running argmax, the
# winner's V / Cholesky columns and, windowed, the (w, w) window factor
# and ring ids) is carried in constant-index cells that stay
# VMEM-resident across the whole grid: the per-step JAX-level winner
# gather / row write-back of the per-step path disappears entirely.
#
# Caveat (ROADMAP speed item 4): interpret mode keeps every output block
# live for the whole grid, so revisited blocks read back the bits the
# previous sweep wrote.  Compiled Mosaic guarantees that only for
# consecutive revisits, i.e. a single whole-M tile; the multi-tile
# schedule is fenced by _require_interpret_for_multitile.
# ---------------------------------------------------------------------------


def _reduce_argmax_and_cols(i, d2, V, C, mx_ref, am_ref, wv_ref, wc_ref,
                            tile_m):
    """The running (max, argmax) fold of :func:`_reduce_running_argmax`
    extended to also capture the running winner's columns — its
    ``V[:, j]`` as a (D, 1) column in ``wv_ref`` and its post-update
    Cholesky column as a (R, 1) column in ``wc_ref`` — so the next sweep
    starts with the winner's columns already VMEM-resident."""

    @pl.when(i == 0)
    def _():
        mx_ref[...] = jnp.full(mx_ref.shape, NEG_INF, jnp.float32)
        am_ref[...] = jnp.zeros(am_ref.shape, jnp.int32)
        wv_ref[...] = jnp.zeros(wv_ref.shape, jnp.float32)
        wc_ref[...] = jnp.zeros(wc_ref.shape, jnp.float32)

    lm, jl = _argmax_first(d2)
    cur = mx_ref[...]
    better = lm > cur
    mx_ref[...] = jnp.where(better, lm, cur)
    am_ref[...] = jnp.where(better, jl + i * tile_m, am_ref[...])
    wv_ref[...] = jnp.where(better, _lane_pick(V, jl), wv_ref[...])
    wc_ref[...] = jnp.where(better, _lane_pick(C, jl), wc_ref[...])


def _emit(sel_ref, dh_ref, s, stopped, j, dj):
    """Write step ``s``'s selection into the (1, chunk) output cells."""
    sel_ref[...] = _lane_set(
        sel_ref[...], s, jnp.where(stopped, -1, j).astype(jnp.int32)
    )
    dh_ref[...] = _lane_set(
        dh_ref[...], s, jnp.where(stopped, 0.0, dj).astype(jnp.float32)
    )


def _chunk_pass_full(
    v_ref, cin_ref, d2in_ref, f0_ref, i0_ref, vj0_ref, cj0_ref,
    cout_ref, d2out_ref, sel_ref, dh_ref,
    stepf_ref, stepi_ref, wvc_ref, wcc_ref,
    mxn_ref, amn_ref, wvn_ref, wcn_ref,
    *, eps: float, tile_m: int,
):
    """One (step, tile) grid cell of the fused exact chunk.

    Inputs: V tile (D, TM); C/d2 state tiles (read at sweep 0 only —
    later sweeps read the revisited output blocks); f0 (1, 2) f32
    [dj2_0, stopped_0], i0 (1, 2) i32 [j_0, t0] and the winner's
    columns vj0 (D, 1) / cj0 (R, 1), all computed at the JAX level once
    per chunk from the resumable state.

    Cells: stepf (1, 2) [d_j, stopped] and stepi (1, 2) [j, t0] hold
    the *current* step's scalars (written by tile 0, read by every
    tile); wvc/wcc the current winner's columns; mxn/amn/wvn/wcn the
    running argmax + columns feeding the *next* sweep.
    """
    s = pl.program_id(1)
    i = pl.program_id(2)
    eps2 = eps * eps
    first = s == 0

    @pl.when(i == 0)
    def _setup():
        f0, i0 = f0_ref[...], i0_ref[...]
        dj2 = jnp.where(first, _lane_pick(f0, 0), _cell(mxn_ref))
        prev_stop = jnp.where(
            first, _lane_pick(f0, 1), _lane_pick(stepf_ref[...], 1)
        ) > 0
        j = jnp.where(first, _lane_pick(i0, 0), _cell(amn_ref))
        stopped = jnp.logical_or(prev_stop, dj2 <= eps2)
        dj = jnp.sqrt(jnp.maximum(dj2, eps2))
        stepf_ref[...] = _lane_row([dj, stopped.astype(jnp.float32)], 2)
        stepi_ref[...] = _lane_row([j, _lane_pick(i0, 1)], 2)
        wvc_ref[...] = jnp.where(first, vj0_ref[...], wvn_ref[...])
        wcc_ref[...] = jnp.where(first, cj0_ref[...], wcn_ref[...])
        _emit(sel_ref, dh_ref, s, stopped, j, dj)

    stepf, stepi = stepf_ref[...], stepi_ref[...]
    dj = _lane_pick(stepf, 0)
    stopped = _lane_pick(stepf, 1) > 0
    j = _lane_pick(stepi, 0)
    t = _lane_pick(stepi, 1) + s
    C = jnp.where(first, cin_ref[...], cout_ref[...])
    d2 = jnp.where(first, d2in_ref[...], d2out_ref[...])
    V = v_ref[...]
    e, d2o = _tile_update_full(
        V, C, d2, wvc_ref[...], wcc_ref[...], dj, stopped, j, 0, i, tile_m,
    )
    # append the new Cholesky row in place (row t; zeros once stopped,
    # exactly as the per-step driver's dynamic_update_slice writes)
    Cnew = _row_set(C, t, e)
    cout_ref[...] = Cnew
    d2out_ref[...] = d2o
    _reduce_argmax_and_cols(
        i, d2o, V, Cnew, mxn_ref, amn_ref, wvn_ref, wcn_ref, tile_m
    )


def _chunk_pass_windowed(
    v_ref, cin_ref, d2in_ref, f0_ref, i0_ref, vj0_ref, cj0_ref,
    cw0_ref, win0_ref,
    cout_ref, d2out_ref, sel_ref, dh_ref,
    stepf_ref, stepi_ref, wvc_ref, wcp_ref, cwc_ref, wring_ref,
    mxn_ref, amn_ref, wvn_ref, wcn_ref,
    *, eps: float, w: int, tile_m: int,
):
    """One (step, tile) grid cell of the fused sliding-window chunk.

    Beyond the exact variant, two more resident cells track the window
    through the chunk: ``cwc (w, w)`` — the window factor ``C[:, win]``
    (maintained by applying the same eviction rotations the tiles apply
    to their columns, its appended row filled in by whichever tile owns
    each window member) — and ``wring (1, w)`` — the ring ids.  Tile 0
    derives the step's eviction rotations from these cells with
    :func:`_evict_coeffs_tile` (the identical recurrence the per-step
    JAX driver uses), so no JAX-level gather happens inside a chunk.
    """
    s = pl.program_id(1)
    i = pl.program_id(2)
    eps2 = eps * eps
    first = s == 0

    @pl.when(i == 0)
    def _setup():
        f0, i0 = f0_ref[...], i0_ref[...]
        dj2 = jnp.where(first, _lane_pick(f0, 0), _cell(mxn_ref))
        prev_stop = jnp.where(
            first, _lane_pick(f0, 1), _lane_pick(stepf_ref[...], 1)
        ) > 0
        j = jnp.where(first, _lane_pick(i0, 0), _cell(amn_ref))
        t0 = _lane_pick(i0, 1)
        t = _lane_pick(t0 + s, 0)  # re-reduced: see _cell
        stopped = jnp.logical_or(prev_stop, dj2 <= eps2)
        dj = jnp.sqrt(jnp.maximum(dj2, eps2))
        full = jnp.logical_and(t >= w, jnp.logical_not(stopped))
        cj_pre = jnp.where(first, cj0_ref[...], wcn_ref[...])  # (w, 1)
        Cw = jnp.where(first, cw0_ref[...], cwc_ref[...])  # (w, w)
        W = jnp.where(first, win0_ref[...], wring_ref[...])  # (1, w) i32
        coss, sins, cj_post, d2j = _evict_coeffs_tile(
            Cw, cj_pre, dj2, full, w
        )
        djp = jnp.sqrt(jnp.maximum(d2j, eps2))
        pos = jnp.minimum(t, w - 1)
        stepf_ref[...] = _lane_row(
            [djp, stopped.astype(jnp.float32), full.astype(jnp.float32)]
            + coss + sins,
            stepf_ref.shape[-1],
        )
        stepi_ref[...] = _lane_row([j, pos, t0], 3)
        wvc_ref[...] = jnp.where(first, vj0_ref[...], wvn_ref[...])
        wcp_ref[...] = cj_post

        # maintain the (w, w) window factor through evict + append:
        # rotate its rows with the step's coefficients (the same
        # recurrence the tiles apply to their columns) ...
        full_s, stop_s, pos_s = _flag(full), _flag(stopped), _index(pos)
        zero_w = jnp.zeros((1, w), jnp.float32)
        u_w = jnp.where(full_s, _row_pick(Cw, 0), zero_w)
        rotated = jnp.zeros((w, w), jnp.float32)
        for r in range(w - 1):
            row = jnp.where(full_s, _row_pick(Cw, r + 1), _row_pick(Cw, r))
            rotated = _row_set(rotated, r, coss[r] * row + sins[r] * u_w)
            u_w = coss[r] * u_w - sins[r] * row
        rotated = _row_set(
            rotated, w - 1, jnp.where(full_s, zero_w, _row_pick(Cw, w - 1))
        )
        # ... shift out the evicted member's column / enter the winner's
        shifted = _lane_set(rotated, w - 1, cj_post)
        W_shift = jnp.full((1, w), -1, jnp.int32)
        for c in range(w - 1):
            shifted = _lane_set(shifted, c, _lane_pick(rotated, c + 1))
            W_shift = _lane_set(W_shift, c, _lane_pick(W, c + 1))
        not_full = _lane_set(rotated, pos_s, cj_post)
        Cw_new = jnp.where(full_s, shifted, not_full)
        # row pos is the appended e-row: zero it here, the owning tiles
        # fill in e[win_r] for their members during the sweep
        Cw_new = _row_set(Cw_new, pos_s, 0.0)
        cwc_ref[...] = jnp.where(stop_s, Cw, Cw_new)

        W1 = jnp.where(full_s, W_shift, W)
        wring_ref[...] = jnp.where(stop_s, W, _lane_set(W1, pos_s, j))
        _emit(sel_ref, dh_ref, s, stopped, j, dj)

    stepf = stepf_ref[...]
    djp = _lane_pick(stepf, 0)
    stopped = _lane_pick(stepf, 1) > 0
    full = _lane_pick(stepf, 2) > 0
    coss = [_lane_pick(stepf, 3 + r) for r in range(w - 1)]
    sins = [_lane_pick(stepf, 3 + (w - 1) + r) for r in range(w - 1)]
    stepi = stepi_ref[...]
    j = _lane_pick(stepi, 0)
    pos = _lane_pick(stepi, 1)
    C = jnp.where(first, cin_ref[...], cout_ref[...])
    d2 = jnp.where(first, d2in_ref[...], d2out_ref[...])
    V = v_ref[...]
    C_out, d2o, e = _tile_update_windowed(
        V, C, d2, wvc_ref[...], wcp_ref[...], djp, stopped, full,
        coss, sins, j, 0, pos, i, w, tile_m,
    )
    cout_ref[...] = C_out
    d2out_ref[...] = d2o

    # fill the appended window-factor row: e[win_r] for the members this
    # tile owns (each global id lives in exactly one tile)
    W_new = wring_ref[...]
    Cw = cwc_ref[...]
    at_pos = _iota((w, w), 0) == _index(pos)
    for r in range(w):
        idx = _lane_pick(W_new, r)
        loc = idx - i * tile_m
        owned = (idx >= 0) & (loc >= 0) & (loc < tile_m) & jnp.logical_not(
            stopped
        )
        cur = _lane_pick(_row_pick(Cw, pos), r)
        val = jnp.where(owned, _lane_pick(e, loc), cur)
        Cw = jnp.where(at_pos & (_iota((w, w), 1) == r), val, Cw)
    cwc_ref[...] = Cw

    _reduce_argmax_and_cols(
        i, d2o, V, C_out, mxn_ref, amn_ref, wvn_ref, wcn_ref, tile_m
    )


def _ctile_spec(rows, tile_m):
    return pl.BlockSpec((None, rows, tile_m), lambda b, s, i: (b, 0, i))


def _ccell_spec(rows, cols):
    return pl.BlockSpec((None, rows, cols), lambda b, s, i: (b, 0, 0))


def _require_interpret_for_multitile(interpret: bool, nt: int) -> None:
    """The fused chunk kernels carry C/d2 across greedy steps in
    *revisited output blocks*: tile block ``i`` is written at grid step
    ``(b, s, i)`` and read again at ``(b, s+1, i)`` with the ``nt - 1``
    other tiles visited in between.  Pallas interpret mode keeps every
    output block live for the whole grid, so the pattern is exact there;
    compiled Mosaic only guarantees a revisited block's contents when
    the revisits are *consecutive* grid steps, which holds only for
    ``nt == 1``.  Until the multi-tile schedule is validated on real
    hardware (ROADMAP: compiled-mode fused chunks), compiling it is an
    error rather than silent wrong slates.  ``repro.analysis``'s
    pallas-revisit-gap rule probes this guard."""
    if not interpret and nt > 1:
        raise NotImplementedError(
            f"fused chunk kernels compile only with a single whole-M tile "
            f"(nt={nt} tiles requested): cross-step state lives in output "
            f"blocks revisited non-consecutively, which compiled Mosaic "
            f"does not guarantee — widen tile_m to cover M, or step with "
            f"the per-step tiled kernels"
        )


def _fused_chunk_call(kernel, *, name, grid, in_specs, out_specs,
                      out_shape, interpret, ins):
    """The single ``pallas_call`` a fused chunk makes.  Kept as a named
    seam so tests can count invocations: one call — one C/d2 HBM
    round-trip — per chunk, however many steps the chunk spans.
    ``name`` names the kernel family in the compiled program
    (``dpp_chunk_exact`` / ``dpp_chunk_windowed``)."""
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*ins)


def pallas_call_structure(jaxpr, in_loop=False, counts=None):
    """Audit a (closed) jaxpr for kernel-launch structure:
    ``{"flat": n, "looped": n}`` pallas_call eqns, split by whether they
    sit under a loop primitive (while/scan).  A looped launch runs once
    per iteration — per greedy step; a flat one exactly once — per
    chunk.  The fused chunk executors above must trace to exactly one
    flat launch and none looped (asserted by tests/test_streaming.py
    and gated by benchmarks/fig6_streaming.py)."""
    if counts is None:
        counts = {"flat": 0, "looped": 0}
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        loop = in_loop or eqn.primitive.name in ("while", "scan")
        if eqn.primitive.name == "pallas_call":
            counts["looped" if loop else "flat"] += 1
        for v in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                v, is_leaf=lambda x: hasattr(x, "eqns")
                or hasattr(x, "jaxpr")
            ):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    pallas_call_structure(sub, loop, counts)
    return counts


def _winner_cols(V, C, d2):
    """JAX-level winner of ``d2 (B, Mp)``: ``(j, dj2, V[:, j] (B, D, 1),
    C[:, j] (B, R, 1))``."""
    j = jnp.argmax(d2, axis=1).astype(jnp.int32)
    dj2 = jnp.take_along_axis(d2, j[:, None], axis=1)[:, 0]
    vj = jnp.take_along_axis(V, j[:, None, None], axis=2)
    cj = jnp.take_along_axis(C, j[:, None, None], axis=2)
    return j, dj2, vj, cj


@functools.partial(
    jax.jit, static_argnames=("chunk", "eps", "tile_m", "interpret")
)
def fused_chunk_exact(V, C, d2, t0, stopped, *, chunk: int, eps: float,
                      tile_m: int, interpret=None):
    """Advance ``chunk`` exact greedy steps in one fused pallas_call.

    V (B, D, Mp) / C (B, R, Mp) / d2 (B, Mp) / stopped (B,), ``t0`` the
    absolute step of the chunk's first selection.  Returns
    ``(C', d2', stopped', sel (B, chunk), dh (B, chunk))``.
    """
    interpret = resolve_interpret(interpret)
    B, D, Mp = V.shape
    R = C.shape[1]
    nt = Mp // tile_m
    _require_interpret_for_multitile(interpret, nt)
    j0, dj20, vj0, cj0 = _winner_cols(V, C, d2)
    f0 = jnp.stack([dj20, stopped.astype(jnp.float32)], axis=1)[:, None, :]
    t0b = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (B,))
    i0 = jnp.stack([j0, t0b], axis=1)[:, None, :]
    kernel = functools.partial(_chunk_pass_full, eps=eps, tile_m=tile_m)
    outs = _fused_chunk_call(
        kernel,
        name="dpp_chunk_exact",
        grid=(B, chunk, nt),
        in_specs=[
            _ctile_spec(D, tile_m), _ctile_spec(R, tile_m),
            _ctile_spec(1, tile_m),
            _ccell_spec(1, 2), _ccell_spec(1, 2),
            _ccell_spec(D, 1), _ccell_spec(R, 1),
        ],
        out_specs=[
            _ctile_spec(R, tile_m), _ctile_spec(1, tile_m),
            _ccell_spec(1, chunk), _ccell_spec(1, chunk),
            _ccell_spec(1, 2), _ccell_spec(1, 2),
            _ccell_spec(D, 1), _ccell_spec(R, 1),
            _ccell_spec(1, 1), _ccell_spec(1, 1),
            _ccell_spec(D, 1), _ccell_spec(R, 1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, R, Mp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Mp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, chunk), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, chunk), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 2), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 2), jnp.int32),
            jax.ShapeDtypeStruct((B, D, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, D, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, R, 1), jnp.float32),
        ],
        interpret=interpret,
        ins=(V, C, d2[:, None, :], f0, i0, vj0, cj0),
    )
    cout, d2out, sel, dh, stepf = outs[:5]
    stopped_out = stepf[:, 0, 1] > 0
    return cout, d2out[:, 0], stopped_out, sel[:, 0], dh[:, 0]


@functools.partial(
    jax.jit, static_argnames=("chunk", "eps", "w", "tile_m", "interpret")
)
def fused_chunk_windowed(V, C, d2, win, t0, stopped, *, chunk: int,
                         eps: float, w: int, tile_m: int, interpret=None):
    """Advance ``chunk`` sliding-window greedy steps in one fused
    pallas_call.  ``C (B, w, Mp)`` is the window ring, ``win (B, w)``
    the ring ids (oldest first).  Returns
    ``(C', d2', win', stopped', sel (B, chunk), dh (B, chunk))``.
    """
    interpret = resolve_interpret(interpret)
    B, D, Mp = V.shape
    nt = Mp // tile_m
    _require_interpret_for_multitile(interpret, nt)
    j0, dj20, vj0, cj0 = _winner_cols(V, C, d2)
    Cw0 = jnp.take_along_axis(C, jnp.clip(win, 0)[:, None, :], axis=2)
    Cw0 = jnp.where((win >= 0)[:, None, :], Cw0, 0.0)  # (B, w, w)
    win0 = win[:, None, :]
    f0 = jnp.stack([dj20, stopped.astype(jnp.float32)], axis=1)[:, None, :]
    t0b = jnp.broadcast_to(jnp.asarray(t0, jnp.int32), (B,))
    i0 = jnp.stack([j0, t0b], axis=1)[:, None, :]
    nf = 3 + 2 * (w - 1)
    kernel = functools.partial(
        _chunk_pass_windowed, eps=eps, w=w, tile_m=tile_m
    )
    outs = _fused_chunk_call(
        kernel,
        name="dpp_chunk_windowed",
        grid=(B, chunk, nt),
        in_specs=[
            _ctile_spec(D, tile_m), _ctile_spec(w, tile_m),
            _ctile_spec(1, tile_m),
            _ccell_spec(1, 2), _ccell_spec(1, 2),
            _ccell_spec(D, 1), _ccell_spec(w, 1),
            _ccell_spec(w, w), _ccell_spec(1, w),
        ],
        out_specs=[
            _ctile_spec(w, tile_m), _ctile_spec(1, tile_m),
            _ccell_spec(1, chunk), _ccell_spec(1, chunk),
            _ccell_spec(1, nf), _ccell_spec(1, 3),
            _ccell_spec(D, 1), _ccell_spec(w, 1),
            _ccell_spec(w, w), _ccell_spec(1, w),
            _ccell_spec(1, 1), _ccell_spec(1, 1),
            _ccell_spec(D, 1), _ccell_spec(w, 1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, w, Mp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Mp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, chunk), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, chunk), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, nf), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 3), jnp.int32),
            jax.ShapeDtypeStruct((B, D, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, w, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, w, w), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, w), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, D, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, w, 1), jnp.float32),
        ],
        interpret=interpret,
        ins=(V, C, d2[:, None, :], f0, i0, vj0, cj0, Cw0, win0),
    )
    cout, d2out, sel, dh, stepf = outs[:5]
    wring = outs[9]
    stopped_out = stepf[:, 0, 1] > 0
    return cout, d2out[:, 0], wring[:, 0], stopped_out, sel[:, 0], dh[:, 0]


# ---------------------------------------------------------------------------
# Whole-slate driver
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("k", "window", "eps", "tile_m", "interpret")
)
def dpp_greedy_tiled(
    V: jnp.ndarray,
    mask: jnp.ndarray,
    k: int,
    window: int | None = None,
    eps: float = 1e-3,
    tile_m: int = 512,
    interpret=None,
):
    """Batched greedy DPP MAP with the candidate axis streamed in tiles.

    V:    (B, D, M) f32, M a multiple of ``tile_m`` (ops.py pads)
    mask: (B, M) float/bool — selectable candidates (padding False)
    Returns (sel (B, k) i32, d_hist (B, k) f32), identical to the
    resident kernels / the jnp oracle.

    The k-step loop runs at the JAX level; each step launches one grid
    sweep (see module docstring).  Unlike the resident kernels the
    Cholesky state round-trips through HBM between steps — that is the
    price of M not fitting in VMEM, and it is streamed, double-buffered
    traffic, not a fallback to unfused jnp.

    The phases carry ``jax.named_scope`` names in the compiled program's
    op metadata, for a profile to be read by: ``greedy.init`` (the
    diagonal and the first argmax), ``greedy.select`` (the pick and the
    winner's V / C column gathers), ``greedy.sweep`` (the kernel launch
    and its operands) and ``greedy.append`` (the Cholesky row or window
    ring write-back).
    """
    B, D, M = V.shape
    if M % tile_m != 0:
        raise ValueError(f"M={M} must be a multiple of tile_m={tile_m}")
    V = V.astype(jnp.float32)
    w = window if (window is not None and window < k) else None
    R = k if w is None else w
    eps2 = eps * eps

    with jax.named_scope("greedy.init"):
        diag = jnp.sum(V * V, axis=1)  # (B, M)
        d2 = jnp.where(mask > 0, diag, NEG_INF)[:, None, :]  # (B, 1, M)
        C = jnp.zeros((B, R, M), jnp.float32)
        sel = jnp.full((B, k), -1, jnp.int32)
        dh = jnp.zeros((B, k), jnp.float32)
        j0 = jnp.argmax(d2[:, 0, :], axis=1).astype(jnp.int32)
        dj20 = jnp.take_along_axis(d2[:, 0, :], j0[:, None], axis=1)[:, 0]
        stopped0 = jnp.zeros((B,), bool)
    zero = jnp.zeros((B,), jnp.int32)

    def select(t, sel, dh, stopped, j, dj2):
        stopped = stopped | (dj2 <= eps2)
        dj = jnp.sqrt(jnp.maximum(dj2, eps2))
        sel = sel.at[:, t].set(jnp.where(stopped, -1, j))
        dh = dh.at[:, t].set(jnp.where(stopped, 0.0, dj))
        vj = jnp.take_along_axis(V, j[:, None, None], axis=2)  # (B, D, 1)
        return sel, dh, stopped, dj, vj

    def step_full(t, carry):
        C, d2, sel, dh, stopped, j, dj2 = carry
        with jax.named_scope("greedy.select"):
            sel, dh, stopped, dj, vj = select(t, sel, dh, stopped, j, dj2)
            cj = jnp.take_along_axis(C, j[:, None, None], axis=2)  # (B, R, 1)
        with jax.named_scope("greedy.sweep"):
            flt = jnp.stack([dj, stopped.astype(jnp.float32)], 1)[:, None, :]
            ints = jnp.stack([j, zero], 1)[:, None, :]
            e, d2, mx, am = _full_sweep(
                V, C, d2, vj, cj, flt, ints, tile_m=tile_m,
                interpret=interpret,
            )
        with jax.named_scope("greedy.append"):
            C = jax.lax.dynamic_update_slice(C, e, (0, t, 0))
        return C, d2, sel, dh, stopped, am[:, 0, 0], mx[:, 0, 0]

    def step_windowed(t, carry):
        C, d2, win, sel, dh, stopped, j, dj2 = carry
        with jax.named_scope("greedy.select"):
            sel, dh, stopped, dj, vj = select(t, sel, dh, stopped, j, dj2)
            cj_pre = jnp.take_along_axis(C, j[:, None, None], axis=2)[:, :, 0]
            Cw = jnp.take_along_axis(C, jnp.clip(win, 0)[:, None, :], axis=2)
            Cw = jnp.where((win >= 0)[:, None, :], Cw, 0.0)
        full = (t >= w) & ~stopped  # (B,)
        pos = jnp.minimum(t, w - 1)
        with jax.named_scope("greedy.sweep"):
            cos, sin, cj_post, d2j = eviction_coeffs(
                Cw, cj_pre, dj2, full, w
            )
            djp = jnp.sqrt(jnp.maximum(d2j, eps2))
            flt = jnp.concatenate(
                [
                    jnp.stack(
                        [djp, stopped.astype(jnp.float32),
                         full.astype(jnp.float32)],
                        1,
                    ),
                    cos, sin,
                ],
                axis=1,
            )[:, None, :]
            ints = jnp.stack([j, zero, zero + pos], 1)[:, None, :]
            C, d2, mx, am = _windowed_sweep(
                V, C, d2, vj, cj_post[:, :, None], flt, ints,
                w=w, tile_m=tile_m, interpret=interpret,
            )
        with jax.named_scope("greedy.append"):
            win_shift = jnp.roll(win, -1, axis=1)
            win1 = jnp.where(
                full[:, None], win_shift.at[:, w - 1].set(-1), win
            )
            win = jnp.where(stopped[:, None], win, win1.at[:, pos].set(j))
        return C, d2, win, sel, dh, stopped, am[:, 0, 0], mx[:, 0, 0]

    if w is None:
        state = (C, d2, sel, dh, stopped0, j0, dj20)
        _, _, sel, dh, _, _, _ = jax.lax.fori_loop(0, k, step_full, state)
    else:
        win0 = jnp.full((B, w), -1, jnp.int32)
        state = (C, d2, win0, sel, dh, stopped0, j0, dj20)
        _, _, _, sel, dh, _, _, _ = jax.lax.fori_loop(
            0, k, step_windowed, state
        )
    return sel, dh
