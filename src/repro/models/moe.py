"""Mixture-of-Experts blocks.

Two execution paths:

* :func:`moe_apply_ragged` — sort-based dispatch + ``jax.lax.ragged_dot``
  grouped matmuls.  Shard-agnostic; used for smoke tests and small runs.
* :func:`moe_apply_ep` — production expert parallelism inside
  ``shard_map``: capacity-based dispatch, ``all_to_all`` over the data
  axis to the expert shards, dense batched matmuls on the MXU, and the
  return ``all_to_all``.  This is the GShard/Switch pattern reworked for
  TPU (dense (E_loc, C_tot, d) @ (E_loc, d, f_loc) contractions instead
  of GPU-style sparse gathers).

Expert weights live as (E, d, f) with logical axes
("experts", "d_model", "d_ff_expert"); the sharding resolver maps
experts->data and d_ff_expert->model under EP.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig, MoEConfig
from repro.models.schema import ParamSpec
from repro.models.layers import mlp_schema, mlp_apply


def moe_schema(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    e: MoEConfig = cfg.moe
    f = e.d_ff_expert
    s = {
        "router": ParamSpec((d, e.n_experts), ("d_model", "experts_r"), init="small"),
        "w_gate": ParamSpec((e.n_experts, d, f), ("experts", "d_model", "d_ff_expert")),
        "w_up": ParamSpec((e.n_experts, d, f), ("experts", "d_model", "d_ff_expert")),
        "w_down": ParamSpec((e.n_experts, f, d), ("experts", "d_ff_expert", "d_model")),
    }
    if e.n_shared_experts:
        s["shared"] = mlp_schema(cfg, d_ff=f * e.n_shared_experts)
    return s


def router_probs(p, xf, e: MoEConfig):
    """xf: (T, d) -> (top_vals (T,k), top_idx (T,k), aux_loss scalar)."""
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, e.top_k)
    top_vals = top_vals / (jnp.sum(top_vals, axis=-1, keepdims=True) + 1e-9)
    # Switch-style load-balance auxiliary loss: E * sum_e f_e * p_e
    pe = jnp.mean(probs, axis=0)                      # mean router prob
    onehot = jax.nn.one_hot(top_idx[:, 0], e.n_experts)
    fe = jnp.mean(onehot, axis=0)                     # fraction routed (top-1)
    aux = e.n_experts * jnp.sum(pe * fe) * e.load_balance_coef
    return top_vals, top_idx, aux


def _shared_out(p, x):
    return mlp_apply(p["shared"], x) if "shared" in p else 0.0


# ---------------------------------------------------------------------------
# Path 1: ragged_dot (shard-agnostic)
# ---------------------------------------------------------------------------


def moe_apply_ragged(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss)."""
    B, S, d = x.shape
    e = cfg.moe
    T = B * S
    xf = x.reshape(T, d)
    top_vals, top_idx, aux = router_probs(p, xf, e)

    flat_e = top_idx.reshape(-1)                       # (T*k,)
    sort_idx = jnp.argsort(flat_e)                     # stable
    tok_idx = sort_idx // e.top_k
    xs = xf[tok_idx]                                   # (T*k, d)
    group_sizes = jnp.bincount(flat_e, length=e.n_experts).astype(jnp.int32)

    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], group_sizes))
    h = h * jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    out = jax.lax.ragged_dot(h, p["w_down"], group_sizes)

    w = top_vals.reshape(-1)[sort_idx][:, None].astype(out.dtype)
    y = jnp.zeros((T, d), out.dtype).at[tok_idx].add(out * w)
    y = y.reshape(B, S, d) + _shared_out(p, x)
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Path 2: expert parallelism with all_to_all (inside shard_map)
# ---------------------------------------------------------------------------


def moe_apply_ep(p, x, cfg: ModelConfig, *, data_axis: str = "data",
                 model_axis: str = "model", replica_axes=("data",),
                 capacity_factor: float = 1.25,
                 comm_dtype=None,
                 scatter_down: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE.  MUST run inside shard_map where:

    * x is the per-shard token slice (B_loc, S, d) — full d_model;
    * p["router"] replicated; expert weights sharded experts->data_axis
      (so the local leaf is (E_loc, d, f_loc)) and d_ff->model_axis.

    Dispatch: per-shard capacity buffers -> all_to_all over data_axis ->
    dense per-expert matmul -> all_to_all back -> weighted combine.
    """
    B, S, d = x.shape
    e = cfg.moe
    n_shards = jax.lax.axis_size(data_axis)
    E, E_loc = e.n_experts, e.n_experts // n_shards
    T = B * S
    xf = x.reshape(T, d)

    top_vals, top_idx, aux = router_probs(p, xf, e)
    aux = jax.lax.pmean(aux, replica_axes)

    # --- capacity-based slotting (sort by expert, position within group)
    cap = max(1, int(-(-capacity_factor * e.top_k * T // E)))
    flat_e = top_idx.reshape(-1)                            # (T*k,)
    sort_idx = jnp.argsort(flat_e)
    sorted_e = flat_e[sort_idx]
    # position of each routed token within its expert group
    seg_pos = jnp.arange(T * e.top_k) - jnp.searchsorted(sorted_e, sorted_e, side="left")
    keep = seg_pos < cap
    tok_idx = sort_idx // e.top_k

    # scatter tokens into (E, cap, d) send buffer (dropped tokens -> 0)
    send_dtype = comm_dtype or xf.dtype
    buf = jnp.zeros((E, cap, d), send_dtype)
    slot_e = jnp.where(keep, sorted_e, 0)
    slot_c = jnp.where(keep, seg_pos, 0)
    contrib = jnp.where(keep[:, None], xf[tok_idx], 0.0).astype(send_dtype)
    buf = buf.at[slot_e, slot_c].add(contrib)

    # --- all_to_all: (E, cap, d) -> (n_shards * cap tokens per local expert)
    # split axis 0 (experts) across shards, concat source shards on axis 1.
    recv = jax.lax.all_to_all(
        buf.reshape(n_shards, E_loc, cap, d), data_axis,
        split_axis=0, concat_axis=0, tiled=False)
    # recv: (n_shards, E_loc, cap, d) — first dim is the source shard
    recv = recv.transpose(1, 0, 2, 3).reshape(E_loc, n_shards * cap, d)

    # --- dense per-expert compute (local experts, local d_ff shard)
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    recv = recv.astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, wg))
    h = h * jnp.einsum("ecd,edf->ecf", recv, wu)
    out = jnp.einsum("ecf,efd->ecd", h, wd)
    out = out.astype(send_dtype)

    n_model = jax.lax.axis_size(model_axis)
    if scatter_down and d % n_model == 0:
        # §Perf it3: reduce-scatter the partial down-proj over the model
        # axis onto the d dim, send a d/n_model slice through the return
        # all_to_all, and all-gather d only at token granularity.
        out = jax.lax.psum_scatter(out, model_axis, scatter_dimension=2,
                                   tiled=True)              # (E_loc, C', d/m)
        d_loc = d // n_model
    else:
        # d_ff is sharded over model_axis -> partial sums
        out = jax.lax.psum(out, model_axis)
        d_loc = d

    # --- all_to_all back to source shards
    back = out.reshape(E_loc, n_shards, cap, d_loc).transpose(1, 0, 2, 3)
    send = jax.lax.all_to_all(back, data_axis, split_axis=0, concat_axis=0,
                              tiled=False)
    send = send.reshape(E, cap, d_loc)                     # (E, cap, d_loc)

    # --- combine: gather each routed token's expert output, weight, sum
    gathered = send[slot_e, slot_c]                        # (T*k, d_loc)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    w = top_vals.reshape(-1)[sort_idx][:, None].astype(gathered.dtype)
    y = jnp.zeros((T, d_loc), gathered.dtype).at[tok_idx].add(gathered * w)
    if d_loc != d:
        y = jax.lax.all_gather(y, model_axis, axis=1, tiled=True)  # (T, d)
    y = y.reshape(B, S, d)
    if "shared" in p:
        # shared-expert d_ff is sharded over model_axis -> partial sum
        y = y + jax.lax.psum(_shared_out(p, x), model_axis)
    return y.astype(x.dtype), aux
