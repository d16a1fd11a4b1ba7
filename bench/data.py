"""A cell's inputs, made from ``--seed`` on the device in one jitted call.

The item pool is shared by every request (one corpus): unit-norm
features ``(M, D)``.  Each of the ``ring`` requests gets its own scores,
``(B, M)``, or ``(M,)`` where a request is one user, drawn as the
configuration's ``score_dist`` says: ``"normal"`` (standard normal
logits of a scorer) or ``"uniform"`` (on [0, 1)), and ``n_masked``
masks ``(M,)`` each mark ``mask_share`` of the pool as seen.
Everything is float32, the type the reranker serves.  Each request's
arrays come out as arrays of their own, so the window never slices.

On a mesh the pool and the scores are made sharded along the candidate
axis, so no chip ever holds the whole pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


DISTS = {"normal": jax.random.normal, "uniform": jax.random.uniform}


def _make(key, M, D, B, ring, n_masked, seen, dist):
    kf, ks, km = jax.random.split(key, 3)
    feats = jax.random.normal(kf, (M, D), jnp.float32)
    feats = feats / jnp.linalg.norm(feats, axis=1, keepdims=True)
    scores = DISTS[dist](ks, (ring, B, M), jnp.float32)
    scores = tuple(scores[r, 0] if B == 1 else scores[r] for r in range(ring))
    masks = ()
    if n_masked:
        u = jax.random.uniform(km, (n_masked, M))
        # exactly `seen` items per mask: the `seen` smallest draws
        cut = jnp.sort(u, axis=1)[:, seen - 1:seen]
        masks = tuple(u[i] > cut[i] for i in range(n_masked))
    return feats, scores, masks


def make_inputs(seed, M, D, B, ring, n_masked=0, mask_share=0.0, mesh=None,
                score_dist="normal"):
    """``(feats (M, D), ring scores, masks)`` as device arrays (tuples of
    ``ring`` and ``n_masked`` arrays); sharded along M over ``mesh``."""
    seen = int(M * mask_share)
    n_masked = n_masked if seen else 0
    fn = functools.partial(_make, M=M, D=D, B=B, ring=ring,
                           n_masked=n_masked, seen=seen, dist=score_dist)
    shardings = None
    if mesh is not None:
        along_m = NamedSharding(mesh, P("data") if B == 1
                                else P(None, "data"))
        shardings = (NamedSharding(mesh, P("data", None)),
                     (along_m,) * ring,
                     (NamedSharding(mesh, P("data")),) * n_masked)
    out = jax.jit(fn, out_shardings=shardings)(jax.random.key(seed))
    return jax.block_until_ready(out)
