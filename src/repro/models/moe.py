"""Mixture-of-Experts layer with explicit expert-parallel dispatch.

GShard-style top-k token-choice routing with per-(source-shard, expert)
capacity.  When a mesh is installed (repro.distributed.context) the layer
runs inside ``jax.shard_map``: tokens are data-sharded, experts are
sharded on the "model" axis, and dispatch/return are explicit
``all_to_all`` collectives — the communication pattern is visible to the
roofline pass rather than left to GSPMD's scatter heuristics.

Without a mesh (unit tests / CPU smoke runs) the identical local math
runs with n_expert_shards == 1 and no collectives.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import context as dctx
from repro.models.layers import dense_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01


def moe_init(rng, d_model: int, cfg: MoEConfig, dtype):
    ks = jax.random.split(rng, 4)
    E, F = cfg.n_experts, cfg.d_ff
    s_in, s_out = d_model ** -0.5, F ** -0.5
    return {
        "router": dense_init(ks[0], d_model, E, jnp.float32),
        "wi": jax.random.normal(ks[1], (E, d_model, F), dtype) * s_in,
        "wg": jax.random.normal(ks[2], (E, d_model, F), dtype) * s_in,
        "wo": jax.random.normal(ks[3], (E, F, d_model), dtype) * s_out,
    }


def _local_moe(
    x, p, cfg: MoEConfig, n_shards: int, model_axis: Optional[str],
    psum_mode: bool = False,
):
    """Per-device MoE body. x (T_loc, d). Runs inside shard_map (or plain)."""
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    E_loc = E // n_shards
    cap = max(8, int(cfg.capacity_factor * T * K / E))

    # --- routing (f32) ---
    logits = (x.astype(jnp.float32) @ p["router"]["w"])  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)  # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)  # (E,)
    one_hot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)  # (T, K, E)
    ce = jnp.mean(one_hot.sum(1), axis=0) / K  # fraction routed per expert
    aux = E * jnp.sum(me * ce)

    # --- dispatch: position of each (token, slot) within its expert ---
    flat_e = top_e.reshape(-1)  # (T*K,)
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts  # exclusive prefix
    pos_sorted = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros((T * K,), jnp.int32).at[sort_idx].set(pos_sorted)
    pos = jnp.where(pos < cap, pos, cap)  # cap -> dropped via mode='drop'

    tok_idx = jnp.arange(T * K, dtype=jnp.int32) // K
    buf = jnp.zeros((E, cap, d), x.dtype)
    buf = buf.at[flat_e, pos].set(x[tok_idx], mode="drop")

    # --- expert-parallel compute ---
    if model_axis is not None and n_shards > 1 and not psum_mode:
        # tokens sharded over (dp x model): explicit all_to_all dispatch
        # (E, cap, d) -> (n_shards, E_loc, cap, d) -> a2a -> recv by source
        send = buf.reshape(n_shards, E_loc, cap, d)
        recv = jax.lax.all_to_all(send, model_axis, split_axis=0, concat_axis=0, tiled=False)
        expert_in = jnp.moveaxis(recv, 0, 1).reshape(E_loc, n_shards * cap, d)
        h = jnp.einsum("ecd,edf->ecf", expert_in, p["wi"])
        g = jnp.einsum("ecd,edf->ecf", expert_in, p["wg"])
        expert_out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"])
        back = jnp.moveaxis(expert_out.reshape(E_loc, n_shards, cap, d), 1, 0)
        recv = jax.lax.all_to_all(back, model_axis, split_axis=0, concat_axis=0, tiled=False)
        out_buf = recv.reshape(E, cap, d)
        slot_out = out_buf.at[flat_e, pos].get(mode="fill", fill_value=0.0)
    elif model_axis is not None and n_shards > 1:
        # psum fallback (decode-scale T): tokens replicated over model, each
        # shard computes only its E_loc experts, outputs psum-combined.
        shard = jax.lax.axis_index(model_axis)
        lo = shard * E_loc
        expert_in = jax.lax.dynamic_slice(buf, (lo, 0, 0), (E_loc, cap, d))
        h = jnp.einsum("ecd,edf->ecf", expert_in, p["wi"])
        g = jnp.einsum("ecd,edf->ecf", expert_in, p["wg"])
        expert_out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"])
        loc_e = flat_e - lo  # out-of-range -> dropped by mode='fill'
        slot_out = expert_out.at[loc_e, pos].get(mode="fill", fill_value=0.0)
        slot_out = jax.lax.psum(slot_out, model_axis)
    else:
        h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
        g = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
        out_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"])
        slot_out = out_buf.at[flat_e, pos].get(mode="fill", fill_value=0.0)

    # --- combine: weight slots, sum over K ---
    slot_out = slot_out.reshape(T, K, d) * top_w[..., None].astype(x.dtype)
    return slot_out.sum(axis=1), aux


def moe_apply(p, x: jnp.ndarray, cfg: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar).

    With a mesh installed, tokens are re-partitioned over (data x model)
    for dispatch — every device routes its own token slice to the expert
    owners via all_to_all over the model axis (true expert parallelism:
    no duplicated expert FLOPs across the TP group).  GSPMD inserts the
    cheap reshard (slice on entry, all-gather on exit) at the boundary.
    """
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    mesh = dctx.current_mesh()
    model_axis = dctx.model_axis_name()

    if mesh is None or model_axis is None:
        out, aux = _local_moe(xt, p, cfg, 1, None)
        return out.reshape(B, S, d), aux

    n_shards = mesh.shape[model_axis]
    dp_axes = dctx.data_axis_names()
    T = B * S
    P = jax.sharding.PartitionSpec

    # Token partitioning for dispatch, by preference:
    #   (dp x model)  — full expert parallelism (training / prefill scale);
    #   (model)       — small batches (decode) still use a2a dispatch;
    #   replicated+psum — tiny T (decode with B < model size).
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    if T % (dp_size * n_shards) == 0:
        tok_axes: tuple = tuple(dict.fromkeys(tuple(dp_axes) + (model_axis,)))
        psum_mode = False
    elif T % n_shards == 0:
        tok_axes = (model_axis,)
        psum_mode = False
    else:
        tok_axes = ()
        psum_mode = True

    x_spec = P(tok_axes if tok_axes else None, None)
    p_specs = {
        "router": {"w": P(None, None)},
        "wi": P(model_axis, None, None),
        "wg": P(model_axis, None, None),
        "wo": P(model_axis, None, None),
    }
    pmean_axes = tok_axes if tok_axes else (model_axis,)

    def body(xt_loc, p_loc):
        out, aux = _local_moe(
            xt_loc, p_loc, cfg, n_shards,
            model_axis if n_shards > 1 else None, psum_mode,
        )
        aux = jax.lax.pmean(aux, pmean_axes)
        return out, aux

    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, p_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(xt, p)
    return out.reshape(B, S, d), aux
