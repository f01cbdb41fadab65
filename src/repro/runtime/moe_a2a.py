"""Explicit all-to-all MoE dispatch (shard_map) - the structural fix for
EXPERIMENTS.md §Perf Cell D.

Under automatic SPMD, the GShard one-hot dispatch einsum with tokens
sharded over (data x model) and experts sharded over model lowers to token
*all-gathers* (each expert shard pulls every token) - measured 25% more
collective bytes than baseline TP. The correct pattern is an
**all-to-all**: each source shard packs per-expert capacity buckets and
ships each bucket only to the shard that owns that expert.

Per model-axis shard (inside shard_map):
  1. route local tokens: top-k experts + weights (router is replicated);
  2. scatter tokens into a (E, C_loc, d) capacity buffer (E = global
     expert count, C_loc = local capacity per expert);
  3. ``jax.lax.all_to_all`` over the model axis: (E, C_loc, d) ->
     (E_loc, M * C_loc, d) - every shard now holds exactly the tokens
     bound for ITS experts;
  4. run the local experts' FFN;
  5. reverse all-to-all; combine with routing weights locally.

Bytes per device per layer: 2 x (top_k * T_loc * cf * d) - independent of
the expert count, vs the gather formulation's E-fold token replication.

Numerics match ``models.moe.apply_moe_dense`` exactly when capacity is
sufficient (drop-free); validated on a 4-device mesh in
tests/test_distributed_moe.py.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.moe import MoEConfig


def _local_dispatch(x, top_w, top_i, n_experts: int, capacity: int):
    """Scatter local tokens into per-expert capacity buckets.

    x: (T, d); top_w/top_i: (T, k).  Returns (buf (E, C, d),
    slot_of (T, k) int32 [-1 if dropped], kept (T, k) bool)."""
    T, k = top_i.shape
    flat_e = top_i.reshape(-1)                      # (T*k,)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot       # position within expert
    slot = jnp.sum(pos * onehot, axis=1)            # (T*k,)
    kept = slot < capacity
    dest = jnp.where(kept, flat_e * capacity + slot, n_experts * capacity)
    buf = jnp.zeros((n_experts * capacity + 1, x.shape[-1]), x.dtype)
    src = jnp.repeat(x, k, axis=0)                  # (T*k, d)
    buf = buf.at[dest].set(src)                     # drops land in the pad row
    return (buf[:-1].reshape(n_experts, capacity, x.shape[-1]),
            jnp.where(kept, slot, -1).reshape(T, k),
            kept.reshape(T, k))


def make_moe_a2a(mesh: Mesh, cfg: MoEConfig, mlp_kind: str, d_model: int,
                 axis: str = "model", dp_axis: str = "data"):
    """Returns fn(params, x) -> (out, aux) running expert-parallel MoE with
    explicit all-to-alls.  params: as ``models.moe.init_moe`` but with the
    expert leaves sharded (E_loc, ...) over ``axis``; x: (B, S, d) with
    batch sharded over ``dp_axis``."""
    from repro.models.layers import apply_mlp
    from repro.models.moe import router_probs

    M = mesh.shape[axis]
    assert cfg.n_experts % M == 0, (cfg.n_experts, M)
    e_loc = cfg.n_experts // M

    def shard_fn(params, x):
        B, S, D = x.shape
        T = B * S
        xt = x.reshape(T, D)
        gates, top_w, top_i = router_probs(params, xt, cfg)
        capacity = max(int(math.ceil(cfg.top_k * T * cfg.capacity_factor
                                     / cfg.n_experts)), cfg.top_k)
        buf, slot, kept = _local_dispatch(xt, top_w, top_i,
                                          cfg.n_experts, capacity)
        # (E, C, d) -> (e_loc, M*C, d): expert blocks are contiguous, so a
        # tiled all-to-all ships block m to shard m and concatenates the M
        # incoming capacity buckets for MY experts
        recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                                  tiled=True)

        def per_expert(ep, xin):
            return apply_mlp(ep, xin, mlp_kind)

        out_loc = jax.vmap(per_expert)(params["experts"], recv)
        # reverse: (e_loc, M*C, d) -> (E, C, d) rows back to their sources
        sent = jax.lax.all_to_all(out_loc, axis, split_axis=1, concat_axis=0,
                                  tiled=True)
        # gather my tokens' results and combine with routing weights
        flat_e = top_i.reshape(-1)
        flat_s = jnp.maximum(slot.reshape(-1), 0)
        vals = sent[flat_e, flat_s]                  # (T*k, d)
        vals = vals * kept.reshape(-1, 1).astype(vals.dtype)
        w = top_w.reshape(-1, 1).astype(vals.dtype)
        out = jnp.sum((vals * w).reshape(T, cfg.top_k, D), axis=1)
        if "shared" in params:
            out = out + apply_mlp(params["shared"], xt, mlp_kind)
        from repro.models.moe import load_balance_loss
        aux = load_balance_loss(gates, top_i, cfg.n_experts)
        aux = jax.lax.pmean(jax.lax.pmean(aux, dp_axis), axis)
        return out.reshape(B, S, D), aux

    def specs_for(params):
        def assign(path, leaf):
            pstr = "/".join(str(getattr(q, "key", q)) for q in path)
            if "experts" in pstr:
                return P(*(("model",) + (None,) * (leaf.ndim - 1)))
            return P(*((None,) * leaf.ndim))
        return jax.tree_util.tree_map_with_path(assign, params)

    def fn(params, x):
        # tokens partitioned over BOTH axes (EP+DP): each shard routes and
        # dispatches only its own tokens - this is what the automatic-SPMD
        # formulation failed to express (it gathered instead)
        tok_spec = P((dp_axis, axis), None, None)
        in_specs = (specs_for(params), tok_spec)
        return shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=(tok_spec, P()),
                         check_vma=False)(params, x)

    return fn
