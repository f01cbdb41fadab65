"""Distributed collectives: hierarchical gradient reduction, compressed
cross-pod exchange, and the distributed split-KV decode combine.

These are the shard_map building blocks behind the perf levers recorded in
EXPERIMENTS.md section Perf:

* ``hierarchical_allreduce`` - reduce-scatter inside the pod (cheap ICI),
  exchange only 1/|data| of the gradient across pods, all-gather back.
  Cross-pod bytes: 2/|data| of a flat all-reduce.
* int8 cross-pod compression (+ error feedback in the optimizer wrapper) -
  the S-Paxos control/data split: tiny f32 scales ride with int8 payloads.
* ``distributed_flash_decode_combine`` - merges per-shard (m, l, acc)
  partial attention over a sequence-sharded KV cache with one psum
  (log-sum-exp algebra); the multi-chip form of kernels/decode_attention.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.optim.compression import dequantize_int8, quantize_int8


def hierarchical_allreduce(x: jnp.ndarray, *, in_pod_axis: str = "data",
                           cross_pod_axis: Optional[str] = "pod",
                           compress_cross_pod: bool = False) -> jnp.ndarray:
    """Mean-reduce ``x`` over (pod, data) inside a shard_map region.

    reduce_scatter(in-pod) -> [quantize] -> psum(cross-pod) -> [dequantize]
    -> all_gather(in-pod).  Equivalent to psum over both axes (up to int8
    rounding when compression is on), with cross-pod traffic reduced by
    |data| x (and a further 4x with int8)."""
    n_in = jax.lax.psum(1, in_pod_axis)
    shard = jax.lax.psum_scatter(x, in_pod_axis, scatter_dimension=0,
                                 tiled=True)
    if cross_pod_axis is not None:
        if compress_cross_pod:
            q, scale = quantize_int8(shard)
            q_sum = jax.lax.psum(q.astype(jnp.int32), cross_pod_axis)
            scale = jax.lax.pmax(scale, cross_pod_axis)
            shard = (q_sum.astype(jnp.float32) * scale).astype(shard.dtype)
        else:
            shard = jax.lax.psum(shard, cross_pod_axis)
    out = jax.lax.all_gather(shard, in_pod_axis, axis=0, tiled=True)
    n_cross = (jax.lax.psum(1, cross_pod_axis)
               if cross_pod_axis is not None else 1)
    return out / (n_in * n_cross)


def make_hierarchical_grad_mean(mesh: Mesh, compress_cross_pod: bool = False):
    """Returns a jit-able fn averaging a replicated-gradient pytree over all
    data axes via shard_map (for gradients produced per-DP-rank)."""
    has_pod = "pod" in mesh.axis_names

    def one(g):
        flat = g.reshape(-1)
        pad = (-flat.shape[0]) % mesh.shape["data"]
        flat = jnp.pad(flat, (0, pad))
        out = hierarchical_allreduce(
            flat, in_pod_axis="data",
            cross_pod_axis="pod" if has_pod else None,
            compress_cross_pod=compress_cross_pod)
        return out[:g.size].reshape(g.shape)

    def grad_mean(grads):
        return jax.tree.map(one, grads)

    spec = P()  # gradients replicated per rank inside the region
    return jax.jit(
        shard_map(grad_mean, mesh=mesh, in_specs=spec, out_specs=spec,
                  check_vma=False))


# ---------------------------------------------------------------------------
# distributed split-KV flash decode
# ---------------------------------------------------------------------------


def flash_decode_partial(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         valid: jnp.ndarray
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-shard partial attention.  q: (B, H, d); k/v: (B, S_loc, H_kv, d);
    valid: (B, S_loc) bool.  Returns (m, l, acc) with shapes
    ((B, H, 1), (B, H, 1), (B, H, d))."""
    import math
    B, H, D = q.shape
    H_kv = k.shape[2]
    group = H // H_kv
    qg = q.reshape(B, H_kv, group, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k.astype(jnp.float32))
    s = s / math.sqrt(D)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)            # (B, H_kv, g, 1)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return (m.reshape(B, H, 1), l.reshape(B, H, 1), acc.reshape(B, H, D))


def combine_partials(m, l, acc, axis: str) -> jnp.ndarray:
    """Merge per-shard softmax partials over a mesh axis with psums."""
    m_glob = jax.lax.pmax(m, axis)
    corr = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * corr, axis)
    acc_glob = jax.lax.psum(acc * corr, axis)
    return acc_glob / jnp.maximum(l_glob, 1e-30)


def make_distributed_flash_decode(mesh: Mesh, seq_axis: str = "model",
                                  batch_axes=("data",)):
    """Decode attention over a sequence-sharded KV cache.

    q is replicated over the sequence axis; each shard computes its partial
    and one (m,l,acc) psum of size O(B*H*d) merges them - instead of
    all-gathering an O(B*S*H_kv*d) cache."""

    def fn(q, k_cache, v_cache, cache_len):
        # local positions owned by this shard
        idx = jax.lax.axis_index(seq_axis)
        s_loc = k_cache.shape[1]
        start = idx * s_loc
        pos = start + jnp.arange(s_loc)[None, :]
        valid = pos < cache_len[:, None]
        m, l, acc = flash_decode_partial(q, k_cache, v_cache, valid)
        return combine_partials(m, l, acc, seq_axis)

    b = batch_axes
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(b, None, None), P(b, seq_axis, None, None),
                  P(b, seq_axis, None, None), P(b)),
        out_specs=P(b, None, None),
        check_vma=False)
