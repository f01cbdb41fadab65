"""Masked latency histogramming as a Pallas TPU kernel.

The batched execution plane (``repro.core.batched_execution``) emits one
(latency, valid) sample per protocol step per lane; turning those streams
into p50/p99 surfaces means binning every sample against its lane's
log-spaced edge vector - the same ``searchsorted(edges) - 1`` convention
``transient.py`` uses, so quantiles read identically across planes.

The bin update is a scatter-add in spirit, but TPUs hate scatters, so the
kernel counts with comparisons only - pure VPU work, one HBM read of the
samples and one write of the histogram per lane:

* each sample's bin is ``#{j : edges_j < lat} - 1``, clamped to the end
  bins, accumulated one edge at a time;
* each bin's count is a masked sum over the sample axis.

Layout follows the TPU's (8, 128) tiling.  Lanes (one lane = one config x
seed client stream) ride the sublane axis in blocks of 8; samples ride the
128-wide lane axis in fixed tiles; edges and bins are padded to multiples
of 128.  The grid is (lane blocks, sample tiles).  The sample axis is
"arbitrary": each tile adds its counts into the (8, bins) output block,
which stays resident in VMEM, so VMEM holds one (8, tile) slab per input
whatever the lane length.  Padding is inert: padded samples are invalid,
padded edges are +inf (never below a sample), and padded lanes and bins
are sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES = 8
_LANES = 128
_TILE = 2048            # samples per grid step, a multiple of _LANES


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _hist_kernel(s_ref, v_ref, e_ref, o_ref, *, n_edges: int, n_bins: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    lat = s_ref[...]                   # (8, tile) latencies
    valid = v_ref[...] > 0             # (8, tile) real-sample mask
    edges = e_ref[...]                 # (8, E) ascending, +inf padded
    edge_col = jax.lax.broadcasted_iota(jnp.int32, edges.shape, 1)

    def count_below(j, below):
        edge_j = jnp.max(jnp.where(edge_col == j, edges, -jnp.inf),
                         axis=1, keepdims=True)             # (8, 1)
        return below + (edge_j < lat).astype(jnp.int32)

    below = jax.lax.fori_loop(0, n_edges, count_below,
                              jnp.zeros(lat.shape, jnp.int32))
    idx = jnp.clip(below - 1, 0, n_bins - 1)
    bin_col = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)

    def count_bin(b, acc):
        # a tile's count never exceeds _TILE, so it is exact in f32
        hits = jnp.sum(jnp.where(valid & (idx == b), 1.0, 0.0),
                       axis=1, keepdims=True)               # (8, 1)
        return acc + jnp.where(bin_col == b, hits, 0.0)

    counts = jax.lax.fori_loop(0, n_bins, count_bin,
                               jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] += counts.astype(jnp.int32)


def latency_hist(samples: jnp.ndarray, valid: jnp.ndarray,
                 edges: jnp.ndarray, *, interpret: bool = False
                 ) -> jnp.ndarray:
    """samples/valid: (L, N); edges: (L, B+1).  Returns (L, B) int32 counts
    of valid samples per bin (out-of-range samples clamp to the end bins,
    matching the transient plane's convention)."""
    L, N = samples.shape
    B = edges.shape[-1] - 1
    assert edges.shape[0] == L and valid.shape == (L, N), (
        samples.shape, valid.shape, edges.shape)
    t = min(_TILE, _round_up(N, _LANES))
    l_pad = _round_up(L, _SUBLANES)
    n_pad = _round_up(N, t)
    e_pad = _round_up(B + 1, _LANES)
    b_pad = _round_up(B, _LANES)
    s = jnp.pad(samples, ((0, l_pad - L), (0, n_pad - N)))
    v = jnp.pad(valid.astype(jnp.float32), ((0, l_pad - L), (0, n_pad - N)))
    e = jnp.pad(edges, ((0, l_pad - L), (0, e_pad - B - 1)),
                constant_values=jnp.inf)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_edges=B + 1, n_bins=B),
        grid=(l_pad // _SUBLANES, n_pad // t),
        in_specs=[
            pl.BlockSpec((_SUBLANES, t), lambda i, k: (i, k)),
            pl.BlockSpec((_SUBLANES, t), lambda i, k: (i, k)),
            pl.BlockSpec((_SUBLANES, e_pad), lambda i, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_SUBLANES, b_pad), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((l_pad, b_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(s, v, e)
    return out[:L, :B]
