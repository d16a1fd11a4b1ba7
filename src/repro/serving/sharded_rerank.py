"""Sharded candidate-axis DPP rerank — slates over millions of candidates.

Same contract as the single-device ``Reranker.rerank`` dispatch but the
candidate axis M is sharded over ``cfg.mesh``'s ``cfg.axis_name``
(``Reranker`` routes here automatically when ``cfg.mesh`` is set):

* the top-C shortlist is a **sharded top-k** (local top-k per shard,
  one small all-gather merge) that produces a selectable *mask* over
  the full candidate axis — features are never gathered into a dense
  (C, D) shortlist in shortlist order;
* greedy MAP runs through ``repro.core.sharded.dpp_greedy_sharded``:
  each device computes on only its (D, M/P) column shard of the scaled
  feature matrix ``V`` and its slice of the Cholesky ring state, with
  one tiny argmax-allreduce + winner-broadcast per step; on a TPU the
  per-device update streams through the tiled Pallas pass
  (``repro.kernels.dpp_greedy.tiled``) at ``cfg.tile_m``, or at the
  tile the shard's shape gives when it is unset, so even M/P shards
  past the VMEM budget stay on the kernel path.

A request batch of B users shares the mesh: ``scores (B, M)`` (features
per-user ``(B, M, D)`` or shared ``(M, D)``) keeps the candidate axis
sharded, the shortlist becomes one batched sharded top-k, and the greedy
loop state grows a leading B axis per device — the per-step collectives
move B values at once instead of running B sequential single-slate
calls.

The front end builds the full (D, M) ``V`` in one compiled program
(``_sharded_v_program``) from whatever layout the features arrive in,
then lays it out along the mesh (per-shard feature feeds are a ROADMAP
item) — the O(M)-per-device scaling claim is about the per-step compute
and device state, not staging memory.

The returned indices are global ids into the original M, identical to
what the single-device ``Reranker.rerank`` (or a ``vmap`` of it) would
select on the same inputs (same argmax sequence; see ``repro.core.sharded``) —
up to argmax ties between *exactly* float-equal marginal gains of
distinct items, where a single-device shortlist narrower than the pool
breaks by score-sorted shortlist position and this path by lowest
global index (measure-zero on continuous scores).  A single-device
whole-pool shortlist keeps id order too, so it breaks ties as this
path does.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.kernel_matrix import map_relevance
from repro.core.sharded import sharded_topk
from repro.obs.dispatch import record_rerank_trace


def _sharded_kernel(scores, feats, cfg, mask, width: Optional[int] = None):
    """Sharded shortlist mask + scaled-feature kernel build — shared by
    the whole-slate ``Reranker.rerank`` dispatch and the chunk-emitting
    ``Reranker.stream`` / router admission paths so every consumer
    diversifies the identical V.  Validates on the host, then dispatches
    one cached compiled program (``_sharded_v_program``).

    ``width`` (the whole-slate path: the mesh greedy's padded candidate
    axis) zero-pads V and the mask, which it then always returns, to
    that many columns and lays both out along ``cfg.axis_name``, as the
    greedy's ``shard_map`` takes them; unset, V keeps the request's M
    columns.
    Returns ``(V (..., D, M), selectability mask or None)``."""
    if cfg.mesh is None:
        raise ValueError(
            "the sharded rerank path needs cfg.mesh (see DPPRerankConfig)"
        )
    if scores.ndim not in (1, 2):
        raise ValueError(
            f"sharded rerank takes scores (M,) or a user batch (B, M), "
            f"got ndim={scores.ndim}"
        )
    batched = scores.ndim == 2
    if feats.ndim != 2 and not (batched and feats.ndim == 3):
        raise ValueError(
            f"feats must be (M, D) (shared) or, with batched scores, "
            f"per-user (B, M, D); got feats ndim={feats.ndim} with "
            f"scores ndim={scores.ndim}"
        )
    if mask is not None and mask.ndim != 1 and not (batched and mask.ndim == 2):
        raise ValueError(
            f"mask must be (M,) (shared) or, with batched scores, "
            f"per-user (B, M); got mask ndim={mask.ndim} with "
            f"scores ndim={scores.ndim}"
        )
    return _sharded_v_program(
        scores, feats, mask, mesh=cfg.mesh, axis_name=cfg.axis_name,
        C=min(cfg.shortlist, scores.shape[-1]), alpha=cfg.alpha, width=width,
    )


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis_name", "C", "alpha", "width")
)
def _sharded_v_program(scores, feats, mask, *, mesh, axis_name, C, alpha,
                       width):
    """The sharded top-k and shortlist mask where ``C < M``, the
    relevance map, the zeroing of non-selectable columns, ``V = (feats *
    rel)^T`` and, with ``width`` set, the pad and the layout along the
    mesh (``_sharded_kernel``)."""
    record_rerank_trace("sharded", "shortlist")
    batched = scores.ndim == 2
    M = scores.shape[-1]
    smask = mask
    if C < M:
        s = scores if mask is None else jnp.where(
            mask, scores, jnp.finfo(scores.dtype).min
        )
        _, top_i = sharded_topk(s, C, mesh=mesh, axis_name=axis_name)
        if batched:
            B = scores.shape[0]
            shortlisted = (
                jnp.zeros((B, M), bool).at[jnp.arange(B)[:, None], top_i].set(True)
            )
        else:
            shortlisted = jnp.zeros((M,), bool).at[top_i].set(True)
        smask = shortlisted if mask is None else shortlisted & mask
    rel = map_relevance(scores.astype(jnp.float32), alpha)
    if smask is not None:
        # non-selectable items (user-masked or shortlisted out) can never
        # enter the slate, but their raw scores still scale columns of V
        # — a NaN/inf relevance on such an item would poison the per-step
        # matvec for everyone.  Zero every column the single-device
        # rerank would never even build (it only gathers the shortlist).
        rel = jnp.where(smask, rel, 0.0)
    if batched and feats.ndim == 2:
        feats = feats[None]  # shared features broadcast over the batch
    V = jnp.swapaxes(feats * rel[..., None], -1, -2)  # (..., D, M)
    if width is None:
        return V, smask
    pad = [(0, 0)] * (V.ndim - 1) + [(0, width - M)]
    V = jnp.pad(V, pad)
    # padding is never selectable; with no mask to pad, each shard makes
    # its own part of the mask, and no mask moves between chips
    lead = scores.shape[:-1]
    if smask is None:
        smask = jnp.broadcast_to(jnp.arange(width) < M, lead + (width,))
    else:
        smask = jnp.pad(jnp.broadcast_to(smask, scores.shape), pad[1:],
                        constant_values=False)
    along_m = lambda x: jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*[None] * (x.ndim - 1), axis_name)))
    return along_m(V), along_m(smask)
