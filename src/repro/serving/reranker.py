"""DPP slate re-ranking as a first-class serving stage.

Any scorer that yields ``(relevance scores, item feature vectors)`` can be
diversified: shortlist the top-C candidates, build the implicit DPP
kernel ``L = Diag(a^r) F^T F Diag(a^r)`` over the shortlist, and run the
paper's fast greedy MAP (Algorithm 1) — all inside the jitted serve step.

All greedy variants are reached through ``repro.core.greedy_map``:

* ``use_kernel=True`` routes through the Pallas kernels (compiled on a
  TPU, interpreted on other platforms); the default jnp path lowers
  through XLA.  Shortlists whose working set fits VMEM run the resident
  whole-slate-in-VMEM kernel; past the budget the tiled streaming
  kernels take over (``tiling.plan_tile`` — there is no silent jnp fallback
  at scale any more), and ``tile_m=`` pins the tile width explicitly.
* ``window=w`` enforces diversity only against the last ``w`` picks
  (the NeurIPS'18 sliding-window variant, O(w M) per step) so the
  serving path can produce long diversified feeds — slates longer than
  the kernel rank keep selecting instead of eps-stopping.
* ``mesh=`` (with ``axis_name=``) shards the candidate axis over a
  device mesh and delegates to ``repro.serving.sharded_rerank`` —
  slates drawn from a candidate set far larger than a single device
  holds, with a sharded top-k shortlist instead of ``jax.lax.top_k``.
  A batched request (scores ``(B, M)``) keeps the candidate axis
  sharded and runs the whole user batch on the mesh at once (batched
  shortlist, batched greedy loop state, one batched collective per
  step).
* ``mask=`` excludes candidates (already-seen / business-filtered
  items) before the shortlist and inside greedy selection; a masked
  item can never appear in the slate.
* ``Reranker.stream`` emits the slate **incrementally**: a generator
  yielding ``chunk_size``-item chunks (global ids + per-chunk d_hist)
  as the greedy loop produces them, instead of blocking until the
  whole slate is selected — the serving shape the paper's windowed
  variant exists for (repulsion only among nearby items means a long
  feed can start rendering after the first chunk).  Chunks concatenate
  exactly to the whole-slate result on every backend; with ``mesh=``
  the chunked state stays device-resident between chunks.

``DPPRerankConfig`` validates itself at construction (mirroring
``GreedySpec``): a nonsensical slate/shortlist/window/eps raises a
``ValueError`` when the config is built, not as a shape or trace error
deep inside the jitted serve step.

**History.** The function-per-shape surface this module grew
(``rerank`` / ``rerank_batch`` / ``rerank_stream``, plus the sharded
twins in ``repro.serving.sharded_rerank``) was superseded by the
session API in ``repro.serving.api`` — ``Reranker(cfg)`` with
``.rerank`` / ``.stream`` / ``.submit`` dispatching on the config and
the request shape.  The functions survived one release as
``DeprecationWarning`` shims and are now **removed** (pinned by
``tests/test_api.py::test_legacy_shims_are_removed``; the
``dead-shim`` rule of ``repro.analysis`` flags any straggler import).
This module keeps only the model-side config and the shortlist/kernel
builder the session API dispatches through.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.dispatch import GreedySpec
from repro.core.kernel_matrix import map_relevance
from repro.obs import ObsConfig
from repro.obs.dispatch import record_shortlist


@dataclasses.dataclass(frozen=True)
class DPPRerankConfig:
    """Model-side serving configuration.

    These are the knobs that shape *compiled* computations — window,
    eps, backend selection (use_kernel / mesh / tile_m), chunk size,
    the relevance trade-off alpha.  The request-side knobs (slate
    length k, shortlist width, candidate mask, deadline) moved to
    ``repro.serving.api.RerankRequest``; the ``slate_size`` /
    ``shortlist`` fields kept here act as *session defaults* for
    requests that do not override them, so pre-split configs keep
    working unchanged.
    """

    slate_size: int = 50  # N (session default; RerankRequest overrides)
    shortlist: int = 1000  # C (session default; RerankRequest overrides)
    alpha: float = 4.0  # trade-off (paper eq. 21); 1.0 = pure diversity
    eps: float = 1e-3
    use_kernel: bool = False  # Pallas path
    window: Optional[int] = None  # sliding diversity window (None = exact)
    mesh: Optional[object] = None  # shard the candidate axis over this mesh
    axis_name: str = "data"  # mesh axis carrying the candidate shards
    # Pallas candidate-axis tile: an explicit LANE multiple, or None
    # (worked out from the shapes)
    tile_m: Optional[int] = None
    chunk_size: Optional[int] = None  # Reranker.stream emission granularity
    obs: Optional[ObsConfig] = None  # observability (installed by Reranker)

    def __post_init__(self):
        if self.slate_size <= 0:
            raise ValueError(f"slate_size must be >= 1, got {self.slate_size}")
        if self.shortlist <= 0:
            raise ValueError(f"shortlist must be >= 1, got {self.shortlist}")
        if self.window is not None and self.window <= 0:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.eps < 0:
            raise ValueError(f"eps must be >= 0, got {self.eps}")
        if self.chunk_size is not None and self.chunk_size <= 0:
            raise ValueError(
                f"chunk_size must be >= 1, got {self.chunk_size}"
            )
        if self.mesh is not None and self.use_kernel:
            raise ValueError(
                "use_kernel (Pallas) and mesh (sharded) are mutually "
                "exclusive rerank backends"
            )
        if self.tile_m is not None:
            from repro.kernels.dpp_greedy.tiling import validate_tile_m

            validate_tile_m(self.tile_m)
            if not self.use_kernel and self.mesh is None:
                raise ValueError(
                    "tile_m= tiles the Pallas kernels — it needs "
                    "use_kernel=True or mesh= (the jnp backend would "
                    "silently ignore it)"
                )

    def greedy_spec(self) -> GreedySpec:
        if self.mesh is not None:
            backend = "sharded"
        elif self.use_kernel:
            backend = "pallas"
        else:
            backend = "jnp"
        return GreedySpec(
            k=self.slate_size,
            window=self.window,
            backend=backend,
            eps=self.eps,
            mesh=self.mesh,
            axis_name=self.axis_name,
            tile_m=self.tile_m,
            # the jnp spec cannot carry a chunk size (its whole-slate
            # path would silently ignore it — GreedySpec rejects that);
            # Reranker.stream passes it to the chunk executor directly
            chunk_size=self.chunk_size if backend != "jnp" else None,
        )


def _shortlist_kernel(scores, feats, cfg, mask):
    """The top-C shortlist and its implicit DPP kernel, for the
    chunk-emitting ``Reranker.stream``, the router's admission and
    sessions; ``Reranker.rerank``'s compiled shortlist program traces
    the same ``_build_shortlist``, so every path diversifies the
    identical V.  Returns
    ``(V (D, C), shortlist mask or None, top_i (C,) global ids)``."""
    C = _shortlist_width(cfg, scores.shape[0])
    return _build_shortlist(scores, feats, mask, C=C, alpha=cfg.alpha)


def _shortlist_width(cfg, M):
    """The shortlist width C for a pool of M, counting the path its
    build takes (``serving_shortlist_total``)."""
    C = min(cfg.shortlist, M)
    record_shortlist("whole_pool" if C == M else "top_k")
    return C


def _build_shortlist(scores, feats, mask, *, C, alpha):
    """``_shortlist_kernel``'s arithmetic for one request, recording
    nothing, so a compiled program can trace it.

    A shortlist that covers the whole pool (C == M) ranks and permutes
    nothing: V is built over the pool in id order, as the sharded path
    builds it, and ``top_i`` is the identity.  The greedy's picks do not
    depend on column order, so the slate is the one the sorted shortlist
    gives, except that an exact tie between two gains goes to the lower
    global id (the sharded path's rule) instead of the higher score."""
    M = scores.shape[0]
    if C == M:
        s, f, m_top = scores, feats, mask
        top_i = jnp.arange(M, dtype=jnp.int32)
    else:
        s = scores if mask is None else jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        s, top_i = jax.lax.top_k(s, C)
        f = feats[top_i]  # (C, D)
        m_top = None if mask is None else mask[top_i]
    rel = map_relevance(s.astype(jnp.float32), alpha)
    if m_top is not None:
        # neither the sentinel score (which only ranks masked items
        # last; alpha < 1 maps it to inf) nor a masked item's own score
        # may reach the kernel — masked columns are zeroed and excluded
        # from selection by the mask
        rel = jnp.where(m_top, rel, 0.0)
    V = (f * rel[:, None]).T  # (D, C)
    return V, m_top, top_i
