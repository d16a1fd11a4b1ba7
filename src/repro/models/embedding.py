"""Sharded EmbeddingBag built from jnp.take + segment-sum (no native
EmbeddingBag in JAX — this IS part of the system, per the assignment).

All categorical fields share one fused table (row-offset per field) so a
single row-sharded parameter covers the whole collection.  Lookup of a
(B, F, H) multi-hot id batch (−1 = padding) returns (B, F, D) bag sums.

Two paths:
  * local (no mesh): one gather + masked sum;
  * sharded (mesh installed): ``shard_map`` over the model axis — each
    shard owns a contiguous row range, gathers locally (out-of-range ids
    masked) and the partial bags are ``psum``-combined.  The all-to-all
    variant (exchange ids, return only hit rows) is the §Perf hillclimb.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import context as dctx


@dataclasses.dataclass(frozen=True)
class EmbeddingSpec:
    vocab_sizes: Tuple[int, ...]  # rows per field
    dim: int
    pad_to_multiple: int = 512  # fused rows padded for even row-sharding

    @property
    def offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.vocab_sizes)[:-1]]).astype(np.int64)

    @property
    def total_rows(self) -> int:
        t = int(sum(self.vocab_sizes))
        m = self.pad_to_multiple
        return (t + m - 1) // m * m


def init_table(rng, spec: EmbeddingSpec, dtype=jnp.float32, scale: float = 0.01):
    return jax.random.normal(rng, (spec.total_rows, spec.dim), dtype) * scale


def _flat_ids(ids: jnp.ndarray, spec: EmbeddingSpec):
    """(B, F, H) field-local ids (−1 pad) -> (B, F, H) fused row ids + mask."""
    offs = jnp.asarray(spec.offsets, jnp.int32)[None, :, None]
    valid = ids >= 0
    return jnp.where(valid, ids + offs, 0), valid


def _local_bag(table, flat, valid):
    emb = jnp.take(table, flat.reshape(-1), axis=0)  # (B*F*H, D)
    emb = emb.reshape(flat.shape + (table.shape[1],))
    emb = emb * valid[..., None].astype(emb.dtype)
    return emb.sum(axis=2)  # (B, F, D)


def embedding_bag(
    table: jnp.ndarray, ids: jnp.ndarray, spec: EmbeddingSpec,
    mode: str = "psum",
):
    """table (rows, D) [row-sharded when a mesh is active], ids (B, F, H)
    -> (B, F, D) bag-summed embeddings.

    mode="psum" (baseline): every model shard computes a dense partial
    (B, F, D) and the partials are psum-combined — simple, but moves
    2 x B x F x D x 4 bytes per device regardless of hit density.

    mode="alltoall" (§Perf): DLRM-style id exchange — each device sends
    only its ids to the row owners (tiny) and receives only the hit rows
    back (B_loc x F x H x D once), then bags locally.  Requires the batch
    to be sharded over the token axes; falls back to psum otherwise.
    """
    flat, valid = _flat_ids(ids, spec)
    mesh = dctx.current_mesh()
    model_axis = dctx.model_axis_name()
    if mesh is None or model_axis is None or mesh.shape.get(model_axis, 1) == 1:
        return _local_bag(table, flat, valid)

    n_shards = mesh.shape[model_axis]
    rows_loc = spec.total_rows // n_shards
    dp_axes = dctx.data_axis_names()
    B = ids.shape[0]
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    batch_axes = tuple(dict.fromkeys(dp_axes)) if (dp_axes and B % dp_size == 0) else ()
    P = jax.sharding.PartitionSpec
    ids_spec = P(batch_axes if batch_axes else None, None, None)

    if mode == "alltoall" and batch_axes and model_axis in batch_axes:
        # DLRM-style: shard table rows over the FULL (data x model) device
        # grid so embedding grads are exact-local after the reverse a2a —
        # no dense table-grad all-reduce across data replicas.
        ex_axes = batch_axes  # joint exchange group
        n_ex = 1
        for a in ex_axes:
            n_ex *= mesh.shape[a]
        rows_ex = spec.total_rows // n_ex

        def body_a2a(table_loc, flat_loc, valid_loc):
            D = table_loc.shape[1]
            Bl, F, H = flat_loc.shape
            n = Bl * F * H
            req = flat_loc.reshape(-1)
            owner = jnp.clip(req // rows_ex, 0, n_ex - 1)
            # rank of each request within its owner bucket (MoE-style)
            sort_idx = jnp.argsort(owner, stable=True)
            sorted_o = owner[sort_idx]
            counts = jnp.bincount(owner, length=n_ex)
            starts = jnp.cumsum(counts) - counts
            pos_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_o]
            pos = jnp.zeros((n,), jnp.int32).at[sort_idx].set(pos_sorted)
            cap = max(8, int(4 * n / n_ex))  # 4x imbalance margin
            pos = jnp.where(pos < cap, pos, cap)
            send = jnp.zeros((n_ex, cap), jnp.int32)
            send = send.at[owner, pos].set(req, mode="drop")
            recv = jax.lax.all_to_all(send[:, None], ex_axes, 0, 0, tiled=False)
            recv = recv.reshape(n_ex, cap)  # requests addressed to me
            me = jnp.zeros((), jnp.int32)
            for a in ex_axes:
                me = me * mesh.shape[a] + jax.lax.axis_index(a)
            local = recv - me * rows_ex
            rows = jnp.take(
                table_loc, jnp.clip(local, 0, rows_ex - 1).reshape(-1), axis=0
            ).reshape(n_ex, cap, D)
            rows = rows * ((local >= 0) & (local < rows_ex))[..., None].astype(rows.dtype)
            back = jax.lax.all_to_all(rows[:, None], ex_axes, 0, 0, tiled=False)
            back = back.reshape(n_ex, cap, D)  # my requests' rows
            got = back.at[owner, pos].get(mode="fill", fill_value=0.0)  # (n, D)
            got = got.reshape(Bl, F, H, D)
            got = got * valid_loc[..., None].astype(got.dtype)
            return got.sum(axis=2)

        return jax.shard_map(
            body_a2a,
            mesh=mesh,
            in_specs=(P(ex_axes, None), ids_spec, ids_spec),
            out_specs=ids_spec,
            check_vma=False,
        )(table, flat, valid)

    def body(table_loc, flat_loc, valid_loc):
        shard = jax.lax.axis_index(model_axis)
        lo = shard * rows_loc
        local = flat_loc - lo
        hit = valid_loc & (local >= 0) & (local < rows_loc)
        emb = jnp.take(table_loc, jnp.clip(local, 0, rows_loc - 1).reshape(-1), axis=0)
        emb = emb.reshape(flat_loc.shape + (table_loc.shape[1],))
        emb = emb * hit[..., None].astype(emb.dtype)
        part = emb.sum(axis=2)
        return jax.lax.psum(part, model_axis)

    # psum path: ids must NOT be sharded over the model axis
    psum_batch = tuple(a for a in batch_axes if a != model_axis)
    ids_spec = P(psum_batch if psum_batch else None, None, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(model_axis, None), ids_spec, ids_spec),
        out_specs=ids_spec,
        check_vma=False,
    )(table, flat, valid)


def embedding_bag_ref(table, ids, spec: EmbeddingSpec):
    """Dense one-hot oracle (tests): bag sum == onehot(ids) @ table."""
    flat, valid = _flat_ids(ids, spec)
    B, F, H = ids.shape
    out = jnp.zeros((B, F, table.shape[1]), table.dtype)
    for h in range(H):
        oh = jax.nn.one_hot(flat[:, :, h], table.shape[0], dtype=table.dtype)
        oh = oh * valid[:, :, h, None].astype(table.dtype)
        out = out + jnp.einsum("bfr,rd->bfd", oh, table)
    return out
