"""Batched closed-loop execution: "measured" as cheap as "modelled".

:func:`repro.core.execution.run_variant` is the ground truth of the
measured plane - a Python event loop over a real message-passing cluster,
linearizability-checked, ~milliseconds per few dozen commands.  Perfect
for parity smoke, hopeless for *surfaces*: the paper's measured
throughput/latency figures sweep config grids x client populations, and
the analytical planes (:meth:`CompiledSweep.mva`, ``.transient``) already
answer those in one jitted call each.  This module closes the gap: it
lowers a registered variant's execution plane into the same
``lax.scan``-over-steps / ``vmap``-over-(config x seed) shape
:mod:`repro.core.transient` uses, so a whole grid of closed-loop client
populations executes in ONE device call and emits *measured* per-station
msgs/cmd plus latency p50/p99 histograms.

How "measured" stays honest
---------------------------
The per-station message costs are **probe-calibrated, not copied from the
table**: for each config the real cluster runs once write-only and (for
mixed workloads) once at the target mix through :func:`run_variant`, at a
probe size and seed disjoint from anything the parity tests compare
against.  The probes yield per-class per-station msgs/cmd vectors
``cost_write``/``cost_read``; the jitted engine then *executes* the
client populations - every lane realizes exactly
``round(n_commands * f_write)`` writes, shuffled per seed and split
round-robin across clients, mirroring :func:`workload_ops` - and the
measured surface is the completion-weighted blend of the probed costs.
Cross-plane agreement with ``run_variant`` at different sizes and seeds
(within each :class:`~repro.core.api.ExecutableSpec`'s tolerances, exact
on its ``exact_stations``) is pinned by ``tests/test_batched_execution``.

Each step is :func:`repro.core.token_scan.step`, the transient plane's
own: stations are FIFO queues draining work at ``dt / d_k`` per step,
commands walking the active stations in canonical slot order.  What this
engine adds is the *class of the command at the head*, which picks each
station's demand (writes traverse the write path's, reads the read
path's), and op budgets: a client whose budget is spent parks (it
heads no queue again), so the run has a makespan - measured throughput is
``n_commands / t_last`` - and the device loop stops once every lane has
drained.  Every completion emits a latency sample, histogrammed post-scan
by the Pallas :func:`repro.kernels.ops.latency_hist` kernel on the
transient plane's bin edges, so p50/p99 read identically across planes.
A second small device step compacts each client's completed samples
(:func:`_completed_latencies`); the host pulls those, the histogram and
the makespans, never the per-step samples of the whole step bound.

Entry points: :func:`run_variant_batched` (one config),
:func:`execute_configs` (any config list, e.g. a sweep's),
:meth:`repro.core.sweep.CompiledSweep.execute` (the compiled-grid method),
and :func:`validate_batched` (measured-vs-analytical parity on the
batched surface, the ``validate_variant`` analogue).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import token_scan, tracing
from .analytical import STATION_ORDER, calibrate_alpha
from .api import Config, ShardingSpec, Workload, resolve_workload, variant_spec
from .execution import StationParity, default_config, run_variant
from .sharding import shard_weights, split_counts
from .sweep import config_variant
from ..kernels.ops import latency_hist

__all__ = [
    "BatchedExecutionResult", "BatchedParityReport", "batched_parity",
    "execute_configs",
    "measured_capacity", "run_variant_batched", "validate_batched",
]


# ---------------------------------------------------------------------------
# Probe calibration: per-class per-station msgs/cmd off the real cluster
# ---------------------------------------------------------------------------


def _probe_costs(name: str, cfg: Config, w: Workload, exe: Any,
                 probe_n: int, probe_seed: int, state_machine: str
                 ) -> Tuple[np.ndarray, np.ndarray, Any]:
    """Calibrate (cost_write[K], cost_read[K], feedback_trace) for one
    config by executing the real cluster.

    The write costs come from a write-only probe run.  Read costs come
    from a probe at the *target* mix, decomposed against the write probe -
    so read-path costs that only exist under concurrent writers (CRAQ's
    dirty-read forwarding) are captured at the mix they occur at."""
    k = len(STATION_ORDER)
    tracing.count("repro.execute.probe_runs")
    t_w = run_variant(name, cfg, replace(w, f_write=1.0),
                      n_commands=probe_n, seed=probe_seed,
                      state_machine=state_machine)
    cost_w = np.asarray(t_w.demand_slots(), dtype=np.float64)[:k]
    if exe.reads_as_writes or w.f_write >= 1.0:
        return cost_w, cost_w.copy(), t_w
    tracing.count("repro.execute.probe_runs")
    t_mix = run_variant(name, cfg, w, n_commands=probe_n,
                        seed=probe_seed + 1, state_machine=state_machine)
    mix = np.asarray(t_mix.demand_slots(), dtype=np.float64)[:k]
    n_wr, n_rd = t_mix.n_writes, probe_n - t_mix.n_writes
    if n_rd == 0:
        return cost_w, cost_w.copy(), t_mix
    cost_r = np.maximum((mix * probe_n - cost_w * n_wr) / n_rd, 0.0)
    return cost_w, cost_r, t_mix


def _class_streams(n_commands: int, f_write: float, n_clients: int,
                   seeds: np.ndarray, base_seed: int
                   ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Per-seed per-client op-class streams: exactly
    ``round(n_commands * f_write)`` writes (class 1), shuffled per seed
    and split round-robin across clients - the same realized mix
    :func:`repro.core.execution.workload_ops` produces, so the write
    count is seed-independent.  Returns (cls[S, N, L] int32,
    budget[N] int32, n_writes)."""
    n_w = round(n_commands * f_write)
    length = max(-(-n_commands // n_clients), 1)
    cls = np.zeros((len(seeds), n_clients, length), dtype=np.int32)
    budget = np.zeros((n_clients,), dtype=np.int32)
    for i in range(n_commands):
        budget[i % n_clients] += 1
    for si, s in enumerate(seeds):
        flags = np.array([1] * n_w + [0] * (n_commands - n_w), np.int32)
        np.random.default_rng([base_seed, int(s)]).shuffle(flags)
        pos = np.zeros((n_clients,), dtype=np.int64)
        for i in range(n_commands):
            c = i % n_clients
            cls[si, c, pos[c]] = flags[i]
            pos[c] += 1
    return cls, budget, n_w


# ---------------------------------------------------------------------------
# The jitted scan engine (one lane = one config x seed client population)
# ---------------------------------------------------------------------------


# Steps per iteration of the execute scan's device loop.  The loop stops at
# the first such boundary after every lane of the batch has drained its op
# budget; the step bound is a multiple of it.
SCAN_CHUNK = 256
# the names of _execute_batch's two lane vmaps, over which a lane asks
# whether any lane of the batch still has ops to complete
LANE_AXES = ("config", "seed")


def _exec_lane(d_w, d_r, entry, nxt, cls_stream, budget, dt, key,
               n_steps: int, n_clients: int, exponential: bool):
    """One lane's initial state, step function and per-step service draws.

    d_w/d_r: [K] per-class service seconds; nxt: [K] tandem routing;
    cls_stream: [N, L] int32 op classes per client; budget: [N].
    ``step(state, (i, draws[i])) -> (state, (fin[N], lat[N]))`` runs step
    ``i``; ``state[:5]`` are the token-scan tokens, ``state[5]`` each
    client's op index and ``state[6:9]`` done_w, done_r and t_last."""
    n_ops = cls_stream.shape[1]
    ring, tokens0, draws = token_scan.start(entry, nxt, key, budget > 0,
                                            n_steps, exponential)

    def step(state, xs):
        tokens, (op_i, done_w, done_r, t_last) = state[:5], state[5:]
        stage, rank = tokens[:2]
        i, draw_i = xs
        t_end = (i + 1).astype(tokens[4].dtype) * dt

        with jax.named_scope("execute.window"):
            cls_cur = jnp.take_along_axis(
                cls_stream, jnp.clip(op_i, 0, n_ops - 1)[:, None],
                axis=1)[:, 0]
            # the head command's class picks each station's service demand
            # (a parked client, at rank -1, heads no queue)
            head_cls = (jnp.zeros_like(d_w, jnp.int32)
                        .at[stage].add(jnp.where(rank == 0, cls_cur, 0),
                                       mode="drop"))
            rate = token_scan.drain_rate(jnp.where(head_cls > 0, d_w, d_r),
                                         dt)

        # a client whose op is its last parks once it completes
        tokens, fin, lat = token_scan.step(tokens, rate, draw_i, t_end,
                                           op_i + 1 >= budget, ring)
        done_w = done_w + jnp.sum((fin & (cls_cur == 1)).astype(jnp.int32))
        done_r = done_r + jnp.sum((fin & (cls_cur == 0)).astype(jnp.int32))
        t_last = jnp.where(jnp.any(fin), t_end, t_last)
        op_i = op_i + fin.astype(jnp.int32)
        return (*tokens, op_i, done_w, done_r, t_last), (fin, lat)

    state0 = (*tokens0, jnp.zeros((n_clients,), jnp.int32),
              jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
              jnp.asarray(0.0))
    return state0, step, draws


def _one_exec_lane(d_w, d_r, entry, nxt, cls_stream, budget, dt, key,
                   n_steps: int, n_clients: int, exponential: bool):
    """Run one lane under ``_execute_batch``'s lane vmaps; returns
    (fin[n_steps, N], lat[n_steps, N], done_w, done_r, t_last).

    The steps run in chunks of ``SCAN_CHUNK`` inside a ``while_loop`` that
    ends once no lane of the batch has ops left (or at ``n_steps``).  The
    count of such lanes is a ``psum`` over ``LANE_AXES``, so the predicate
    is one value for the whole batch: the vmapped loop stays a loop and
    is not turned into a select over both branches.  Each step writes its
    samples in place into the zeroed ``fin``/``lat`` buffers; ``fin``
    stays False on the steps that never ran."""
    if n_steps % SCAN_CHUNK:
        raise ValueError(
            f"n_steps={n_steps} is not a multiple of {SCAN_CHUNK}")
    state0, step, draws = _exec_lane(d_w, d_r, entry, nxt, cls_stream,
                                     budget, dt, key, n_steps, n_clients,
                                     exponential)
    total = jnp.sum(budget)

    def write(carry, xs):
        state, fin, lat = carry
        state, (fin_i, lat_i) = step(state, xs)
        fin = jax.lax.dynamic_update_index_in_dim(fin, fin_i, xs[0], 0)
        lat = jax.lax.dynamic_update_index_in_dim(lat, lat_i, xs[0], 0)
        return (state, fin, lat), None

    def undrained(carry):
        chunk, (state, _, _) = carry
        left = (state[6] + state[7] < total).astype(jnp.int32)
        return ((chunk < n_steps // SCAN_CHUNK)
                & (jax.lax.psum(left, LANE_AXES) > 0))

    def run_chunk(carry):
        chunk, lane = carry
        i0 = chunk * SCAN_CHUNK
        xs = (i0 + jnp.arange(SCAN_CHUNK, dtype=jnp.int32),
              jax.lax.dynamic_slice_in_dim(draws, i0, SCAN_CHUNK))
        return chunk + 1, jax.lax.scan(write, lane, xs)[0]

    lane0 = (state0, jnp.zeros((n_steps, n_clients), bool),
             jnp.zeros((n_steps, n_clients)))
    _, (state_f, fin, lat) = jax.lax.while_loop(
        undrained, run_chunk, (jnp.asarray(0, jnp.int32), lane0))
    done_w, done_r, t_last = state_f[6:9]
    return fin, lat, done_w, done_r, t_last


@partial(jax.jit, static_argnames=("n_clients", "n_steps", "exponential"))
def _execute_batch(d_w, d_r, entry, nxt, cls, budget, dt, seeds,
                   n_clients: int, n_steps: int, exponential: bool):
    """The ONE device call: vmap lanes over configs (M) x seeds (S).

    d_w/d_r: [M, K]; entry: [M]; nxt: [M, K]; cls: [M, S, N, L];
    budget: [M, N]; dt: [M]; seeds: [S].  Returns
    (fin[M, S, n_steps, N] bool, lat[M, S, n_steps, N], done_w[M, S],
    done_r[M, S], t_last[M, S]); the device stops at the first
    ``SCAN_CHUNK`` boundary after every lane has drained its budget, and
    ``fin`` is False on the steps after it."""
    m_ids = jnp.arange(d_w.shape[0], dtype=jnp.int32)

    def per_config(d_w_m, d_r_m, entry_m, nxt_m, cls_m, budget_m, dt_m, mi):
        def per_seed(cls_ms, s):
            key = jax.random.fold_in(jax.random.fold_in(jax.random.key(1),
                                                        mi), s)
            return _one_exec_lane(d_w_m, d_r_m, entry_m, nxt_m, cls_ms,
                                  budget_m, dt_m, key, n_steps, n_clients,
                                  exponential)
        return jax.vmap(per_seed, axis_name=LANE_AXES[1])(cls_m, seeds)

    return jax.vmap(per_config, axis_name=LANE_AXES[0])(
        d_w, d_r, entry, nxt, cls, budget, dt, m_ids)


@partial(jax.jit, static_argnames=("width",))
def _completed_latencies(fin, lat, done, width: int):
    """Each client's completed latency samples, in completion order.

    fin/lat: [M, S, n_steps, N] as ``_execute_batch`` returns them, with
    ``n_steps`` a multiple of ``SCAN_CHUNK``; done: [M, S] the lanes'
    completions.  Returns samples[M, S, N, width]: slot ``j`` of a client
    holds the ``lat`` of its ``j``-th completion, and 0 past its last one
    (a client completes at most ``width`` ops).  The step axis is walked
    one chunk at a time, carrying each client's completions so far, so no
    array the size of ``fin`` is made, and the walk stops once every lane
    has counted ``done``; each slot receives one value, so the samples are
    exact copies."""
    m, s, n_steps, n = fin.shape
    slots = jnp.arange(width, dtype=jnp.int32)

    def pending(carry):
        c, count, _ = carry
        return ((c < n_steps // SCAN_CHUNK)
                & jnp.any(jnp.sum(count, axis=2) < done))

    def chunk(carry):
        c, count, samples = carry
        fin_c = jax.lax.dynamic_slice_in_dim(fin, c * SCAN_CHUNK, SCAN_CHUNK,
                                             axis=2)
        lat_c = jax.lax.dynamic_slice_in_dim(lat, c * SCAN_CHUNK, SCAN_CHUNK,
                                             axis=2)
        hits = fin_c.astype(jnp.int32)
        rank = count[:, :, None, :] + jnp.cumsum(hits, axis=2) - 1
        put = fin_c[..., None] & (rank[..., None] == slots)
        samples = samples + jnp.sum(jnp.where(put, lat_c[..., None], 0.0),
                                    axis=2)
        return c + 1, count + jnp.sum(hits, axis=2), samples

    carry0 = (jnp.asarray(0, jnp.int32), jnp.zeros((m, s, n), jnp.int32),
              jnp.zeros((m, s, n, width), lat.dtype))
    return jax.lax.while_loop(pending, chunk, carry0)[2]


# ---------------------------------------------------------------------------
# Public surface
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BatchedExecutionResult:
    """One batched execution: M configs x S seeds of closed-loop clients.

    ``station_msgs[m]`` is the measured per-station msgs/cmd/server row
    (canonical :data:`STATION_ORDER` columns) - probe-calibrated per-class
    costs blended by the completions the engine realized; it is
    seed-independent because every lane drains its full op budget at the
    exact generator mix.  Latency/throughput are per (config, seed)."""

    configs: Tuple[Config, ...]
    workload: Workload
    n_commands: int
    n_clients: int
    seeds: np.ndarray              # [S]
    station_msgs: np.ndarray       # [M, K] msgs/cmd/server
    n_writes: np.ndarray           # [M] realized writes per lane
    cost_write: np.ndarray         # [M, K] probe-calibrated write costs
    cost_read: np.ndarray          # [M, K] probe-calibrated read costs
    throughput: np.ndarray         # [M, S] cmds/s (n_commands / makespan)
    latency_mean: np.ndarray       # [M, S] seconds
    latency_p50: np.ndarray        # [M, S]
    latency_p99: np.ndarray        # [M, S]
    completed: np.ndarray          # [M, S] ops drained (== lane budget)
    hist: np.ndarray               # [M, S, B]
    bin_edges: np.ndarray          # [M, B + 1]
    dt: np.ndarray                 # [M] seconds per step
    n_steps: int
    alpha: float
    # Shard axis (sharded runs only): rows become M_cfg x n_shards lanes
    # in config-major order; ``lane_config[m]`` / ``lane_shard[m]`` map a
    # lane back to its (config, shard) and ``lane_commands[m]`` is its
    # command budget (largest-remainder split of ``n_commands`` by the
    # shard traffic weights).  All None when no ShardingSpec was given.
    sharding: Optional[ShardingSpec] = None
    lane_config: Optional[np.ndarray] = None   # [M] config index
    lane_shard: Optional[np.ndarray] = None    # [M] shard index
    lane_commands: Optional[np.ndarray] = None  # [M] per-lane op budget
    # Geo axis (``geo=`` runs only, mutually exclusive with sharding):
    # rows become M_cfg x n_regions lanes in config-major order - one
    # closed-loop client population per region, command budgets split by
    # the region client weights.  ``wan_offset[m]`` is the lane's
    # analytical WAN latency excess (repro.core.geo.wan_offsets; zero for
    # a uniform matrix), already folded into latency_mean/p50/p99 and
    # bin_edges.
    geo: Optional[Any] = None
    lane_region: Optional[np.ndarray] = None   # [M] region index
    wan_offset: Optional[np.ndarray] = None    # [M]

    def __len__(self) -> int:
        return len(self.configs)

    def variant(self, m: int) -> str:
        return config_variant(self.configs[m])

    def shard_lanes(self, config_index: int = 0) -> np.ndarray:
        """Row indices of config ``config_index``'s shard (or region)
        lanes - the whole row range when the run was neither sharded nor
        geo-replicated."""
        if self.lane_config is None:
            return np.asarray([config_index])
        return np.nonzero(self.lane_config == config_index)[0]

    def region_latency(self, config_index: int = 0,
                       which: str = "p99") -> Dict[str, float]:
        """Seed-mean latency per client-bearing region for one config
        (geo runs only).  ``which`` is ``"mean"``, ``"p50"`` or
        ``"p99"``."""
        if self.geo is None or self.lane_region is None:
            raise ValueError("region_latency needs a geo= run")
        stat = {"mean": self.latency_mean, "p50": self.latency_p50,
                "p99": self.latency_p99}[which]
        out: Dict[str, float] = {}
        for lane in self.shard_lanes(config_index):
            if self.lane_commands is not None \
                    and self.lane_commands[lane] == 0:
                continue  # no clients in this region
            region = self.geo.regions[int(self.lane_region[lane])]
            out[region] = float(stat[lane].mean())
        return out

    def sharded_throughput(self, config_index: int = 0) -> np.ndarray:
        """Aggregate cmds/s of one config across its shard lanes, per
        seed.  Shard groups are independent clusters draining their
        traffic fractions concurrently, so the system rate is the sum of
        the per-shard rates."""
        return self.throughput[self.shard_lanes(config_index)].sum(axis=0)

    def station_row(self, m: int) -> Dict[str, float]:
        """Measured msgs/cmd/server of config m, keyed by station name
        (nonzero columns only) - the same vocabulary as
        ``ExecutionTrace.station_msgs``."""
        return {STATION_ORDER[k]: float(v)
                for k, v in enumerate(self.station_msgs[m]) if v > 0.0}

    def describe(self, m: int = 0) -> str:
        pairs = ", ".join(f"{s} {d:.2f}"
                          for s, d in self.station_row(m).items())
        return (f"{self.variant(m)}: {self.n_commands} cmds x "
                f"{len(self.seeds)} seeds ({int(self.n_writes[m])} writes); "
                f"msgs/cmd/server: {pairs}; "
                f"p50 {self.latency_p50[m].mean():.2e}s "
                f"p99 {self.latency_p99[m].mean():.2e}s")


@tracing.span("repro.execute")
def execute_configs(
    configs: Sequence[Config],
    workload: Optional[Union[Workload, float]] = None,
    n_commands: int = 48,
    seeds: Union[int, Sequence[int]] = 4,
    n_clients: int = 8,
    alpha: Optional[float] = None,
    probe_n: Optional[int] = None,
    probe_seed: int = 7919,
    exponential_service: bool = False,
    oversample: float = 4.0,
    n_bins: int = 64,
    state_machine: str = "kv",
    max_steps: int = 200_000,
    sharding: Optional[ShardingSpec] = None,
    geo: Optional[Any] = None,
) -> BatchedExecutionResult:
    """Execute a grid of registered-variant configs as one batched device
    call of closed-loop client populations.

    Per config: probe-calibrate per-class per-station message costs off
    the real cluster (:func:`run_variant` at ``probe_n``/``probe_seed``,
    disjoint from reference runs), lower the variant's demand table to
    per-class service times, build per-seed op-class streams at the exact
    generator mix, then run every (config x seed) lane through ONE jitted
    vmapped ``lax.scan`` and histogram the emitted latency samples with
    the Pallas :func:`repro.kernels.ops.latency_hist` kernel.

    ``exponential_service=False`` (default) is the parity mode: service is
    deterministic, the makespan is bounded, and every lane provably drains
    its budget.  ``True`` matches the MVA product-form assumptions for
    latency-surface work.

    With a :class:`~repro.core.api.ShardingSpec` each config expands to
    ``n_shards`` lanes - independent shard groups sharing the config's
    probe calibration, each draining its largest-remainder slice of
    ``n_commands`` (per the shard traffic weights) behind its own client
    population.  Rows of the result are then (config x shard) in
    config-major order; ``lane_config`` / ``lane_shard`` /
    ``lane_commands`` map them back and
    :meth:`BatchedExecutionResult.sharded_throughput` aggregates.

    With a :class:`~repro.core.api.GeoSpec` (mutually exclusive with
    sharding) each config instead expands to ``n_regions`` lanes - one
    closed-loop client population per region, command budgets split by
    the region client weights - and every lane's latency statistics
    (mean/p50/p99/histogram edges) carry the analytical WAN latency
    *excess* of its region (:func:`repro.core.geo.wan_offsets`, same
    units as ``1 / alpha``; exactly zero for a uniform matrix, so
    uniform-geo lanes read today's numbers unchanged).  The queueing
    part stays measured; the WAN part is deterministic wire time the
    step engine has no wires for."""
    with tracing.span("repro.execute.lower"):
        if not configs:
            raise ValueError("execute_configs: empty config list")
        if geo is not None and sharding is not None:
            raise ValueError(
                "execute_configs: geo= and sharding= are mutually exclusive "
                "(region lanes and shard lanes both multiply the row axis)")
        w = resolve_workload(workload, where="execute_configs")
        if isinstance(seeds, (int, np.integer)):
            seeds_arr = np.arange(int(seeds), dtype=np.int32)
        else:
            seeds_arr = np.asarray(list(seeds), dtype=np.int32)
        if seeds_arr.size == 0:
            raise ValueError("execute_configs: need at least one seed")
        n_probe = probe_n if probe_n is not None else n_commands
        k = len(STATION_ORDER)
        n_cfg = len(configs)
        a = alpha if alpha is not None else calibrate_alpha()

        sharded = sharding is not None and sharding.n_shards > 1
        geoed = geo is not None and geo.n_regions > 1
        n_sh = (sharding.n_shards if sharded
                else geo.n_regions if geoed else 1)
        if sharded:
            lane_n = np.tile(
                split_counts(n_commands, shard_weights(sharding, w)),
                n_cfg).astype(np.int64)
        elif geoed:
            lane_n = np.tile(
                split_counts(n_commands,
                             np.asarray(geo.resolved_client_weights())),
                n_cfg).astype(np.int64)
        else:
            lane_n = np.full((n_cfg,), n_commands, dtype=np.int64)
        m = n_cfg * n_sh
        lane_cfg = np.repeat(np.arange(n_cfg), n_sh)
        lane_shard = np.tile(np.arange(n_sh), n_cfg)

        wan_off = np.zeros((m,))
        if geo is not None:
            from .geo import wan_offsets
            for i, raw in enumerate(configs):
                cfg = dict(raw)
                cfg.setdefault("variant", "compartmentalized")
                off = wan_offsets(cfg, geo, workload=w, n_clients=n_clients)
                wan_off[i * n_sh:(i + 1) * n_sh] = np.asarray(off)[:n_sh]

    cost_w = np.zeros((n_cfg, k))
    cost_r = np.zeros((n_cfg, k))
    d_w_cfg = np.zeros((n_cfg, k))
    d_r_cfg = np.zeros((n_cfg, k))
    f_eff = np.zeros((n_cfg,))
    for i, raw in enumerate(configs):
        cfg = dict(raw)
        cfg.setdefault("variant", "compartmentalized")
        name = config_variant(cfg)
        spec = variant_spec(name)
        if spec.executable is None:
            raise ValueError(
                f"config {i}: variant {name!r} declares no execution plane")
        exe = spec.executable
        with tracing.span("repro.execute.probe"):
            cost_w[i], cost_r[i], _ = _probe_costs(
                name, cfg, w, exe, n_probe, probe_seed, state_machine)
        dw_row, dr_row, _ = spec.model(cfg, w).demand_slots()
        d_w_cfg[i, :len(dw_row)] = np.asarray(dw_row[:k]) / a
        d_r_cfg[i, :len(dr_row)] = np.asarray(dr_row[:k]) / a
        f_eff[i] = 1.0 if exe.reads_as_writes else w.f_write

    with tracing.span("repro.execute.streams"):
        # expand configs to lanes: shards of a config share its probe costs
        # and per-command demands - a shard runs the full deployment, it just
        # sees a fraction of the traffic
        cost_w = np.repeat(cost_w, n_sh, axis=0)
        cost_r = np.repeat(cost_r, n_sh, axis=0)
        d_w = np.repeat(d_w_cfg, n_sh, axis=0)
        d_r = np.repeat(d_r_cfg, n_sh, axis=0)
        f_eff = np.repeat(f_eff, n_sh)

        cls_all: List[np.ndarray] = []
        budget_all: List[np.ndarray] = []
        n_writes = np.zeros((m,), dtype=np.int64)
        for i in range(m):
            cls, budget, n_w = _class_streams(int(lane_n[i]), f_eff[i],
                                              n_clients, seeds_arr,
                                              base_seed=probe_seed + i)
            cls_all.append(cls)
            budget_all.append(budget)
            n_writes[i] = n_w
        length = max(c.shape[2] for c in cls_all)
        cls_all = [np.pad(c, ((0, 0), (0, 0), (0, length - c.shape[2])))
                   for c in cls_all]

    with tracing.span("repro.execute.lower"):
        blend = f_eff[:, None] * d_w + (1.0 - f_eff[:, None]) * d_r
        # station activity is a property of the *config's* mix, not of any one
        # shard's integer split: a zero-command lane still routes through its
        # config's active stations (and trivially drains nothing)
        cfg_w = np.zeros((m,), dtype=bool)
        cfg_r = np.zeros((m,), dtype=bool)
        for i in range(n_cfg):
            rows = slice(i * n_sh, (i + 1) * n_sh)
            cfg_w[rows] = bool(n_writes[rows].sum() > 0)
            cfg_r[rows] = bool(n_writes[rows].sum() < int(lane_n[rows].sum()))
        active = ((cfg_w[:, None] & (d_w > 0))
                  | (cfg_r[:, None] & (d_r > 0)))               # [M, K]
        entry, nxt = token_scan.routing(active)
        dt = blend.max(axis=1) / oversample
        if np.any(dt <= 0):
            raise ValueError("a config row has zero effective demand")

        # deterministic makespan bound: each station serves every command at
        # most once, plus one step per (command, station) for instant drains
        d_hot = np.where(active, np.maximum(d_w, d_r), 0.0)
        span = (lane_n + n_clients) * d_hot.sum(axis=1)
        steps = span / dt + (lane_n + n_clients) * active.sum(axis=1)
        margin = 4.0 if exponential_service else 1.3
        n_steps = int(math.ceil(margin * float(steps.max()))) + 8
        # whole chunks of the device loop, bucketed to reuse the jit cache
        n_steps = -(-n_steps // SCAN_CHUNK) * SCAN_CHUNK
        if n_steps > max_steps:
            raise ValueError(
                f"execute_configs: bound of {n_steps} steps exceeds max_steps="
                f"{max_steps}; raise max_steps or shrink the grid")

        edges = token_scan.bin_edges((blend * active).sum(axis=1), n_steps,
                                     dt, n_bins)

    with tracing.span("repro.execute.dispatch"):
        out = _execute_batch(
            jnp.asarray(d_w), jnp.asarray(d_r), jnp.asarray(entry),
            jnp.asarray(nxt), jnp.asarray(np.stack(cls_all)),
            jnp.asarray(np.stack(budget_all)), jnp.asarray(dt),
            jnp.asarray(seeds_arr), n_clients=n_clients, n_steps=n_steps,
            exponential=bool(exponential_service))
    fin, lat, done_w, done_r, t_last = out
    tracing.wait("repro.execute.wait", done_w)

    done_w, done_r = (x.astype(np.int64) for x in tracing.pull(
        "repro.execute.pull", done_w, done_r))
    done = done_w + done_r
    if not np.all(done == lane_n[:, None]):
        short = np.argwhere(done != lane_n[:, None])
        raise RuntimeError(
            f"execute_configs: lanes {short.tolist()} drained "
            f"{done[tuple(short.T)].tolist()} of their op budgets in "
            f"{n_steps} steps - raise oversample margin or max_steps")

    # histogram and compact the completed samples on the device, then pull
    # only those: [M, S, N, L], not every step of the bound
    s = seeds_arr.size
    with tracing.span("repro.execute.hist"):
        hist = latency_hist(lat.reshape(m * s, -1), fin.reshape(m * s, -1),
                            jnp.asarray(np.repeat(edges, s, axis=0)))
        samples = _completed_latencies(fin, lat, done.astype(np.int32),
                                       width=length)
    tracing.wait("repro.execute.wait", hist)
    hist, samples, t_last = tracing.pull(
        "repro.execute.pull", hist, samples, t_last)

    with tracing.span("repro.execute.reduce"):
        hist = hist.reshape(m, s, n_bins)
        if geo is not None:
            # shift the (geometric) bin edges by each lane's deterministic
            # WAN offset AFTER binning: a sample in [e_k, e_k+1) is in
            # [e_k + wan, e_k+1 + wan) of the shifted edges, so histogram
            # and quantiles both read as total (wire + queueing) latency
            edges = edges + wan_off[:, None]

        # steps each lane ran to its last completion (t_last is the end of
        # that step, (i + 1) * dt), against the steps the scan ran them:
        # whole chunks, up to the one holding the last lane's last one
        lane_steps = np.rint(t_last / dt[:, None])
        ran = SCAN_CHUNK * int(math.ceil(lane_steps.max() / SCAN_CHUNK))
        tracing.count("repro.execute.lane_steps", int(lane_steps.sum()))
        tracing.count("repro.execute.scan_lane_steps", m * s * ran)

        # float64 sums of the float32 samples (empty slots add 0)
        lat_sum = samples.astype(np.float64).sum(axis=(2, 3))
        t_last = t_last.astype(np.float64)

        # completion-weighted blend of the probe-calibrated per-class costs:
        # the measured msgs/cmd surface (float64, so exact stations stay exact)
        msgs = (done_w[:, 0, None] * cost_w + done_r[:, 0, None] * cost_r) \
            / np.maximum(lane_n, 1)[:, None]

        return BatchedExecutionResult(
            configs=tuple(dict(configs[int(ci)]) for ci in lane_cfg),
            workload=w,
            n_commands=n_commands,
            n_clients=n_clients,
            seeds=seeds_arr,
            station_msgs=msgs,
            n_writes=done_w[:, 0].copy(),
            cost_write=cost_w,
            cost_read=cost_r,
            throughput=lane_n[:, None] / np.maximum(t_last, 1e-30),
            latency_mean=lat_sum / np.maximum(done, 1) + wan_off[:, None],
            latency_p50=token_scan.quantile_from_hist(hist, edges, 0.50),
            latency_p99=token_scan.quantile_from_hist(hist, edges, 0.99),
            completed=done.astype(np.float64),
            hist=hist,
            bin_edges=edges,
            dt=dt,
            n_steps=n_steps,
            alpha=a,
            sharding=sharding if sharded else None,
            lane_config=lane_cfg if (sharded or geoed) else None,
            lane_shard=lane_shard if sharded else None,
            lane_commands=lane_n if (sharded or geoed) else None,
            geo=geo,
            lane_region=lane_shard if geoed else None,
            wan_offset=wan_off if geo is not None else None,
        )


def run_variant_batched(name: str,
                        config: Optional[Config] = None,
                        workload: Optional[Union[Workload, float]] = None,
                        n_commands: int = 48,
                        seeds: Union[int, Sequence[int]] = 4,
                        n_clients: Optional[int] = None,
                        **kwargs: Any) -> BatchedExecutionResult:
    """One variant config through the batched executor (M = 1): the
    jitted sibling of :func:`repro.core.execution.run_variant`."""
    spec = variant_spec(name)
    if spec.executable is None:
        raise ValueError(
            f"variant {name!r} declares no execution plane; the batched "
            f"executor drives registered executables only")
    cfg = dict(config) if config is not None else default_config(name)
    cfg.setdefault("variant", name)
    n_cl = n_clients if n_clients is not None else spec.executable.n_clients
    return execute_configs([cfg], workload=workload, n_commands=n_commands,
                           seeds=seeds, n_clients=n_cl, **kwargs)


def measured_capacity(name: str,
                      config: Optional[Config] = None,
                      workload: Optional[Union[Workload, float]] = None,
                      n_commands: int = 96,
                      seeds: Union[int, Sequence[int]] = 3,
                      n_clients: Optional[int] = None,
                      **kwargs: Any) -> float:
    """Saturated cmds/s of one variant config off the batched executor:
    the execution-plane twin of the transient capacity anchor that
    :func:`repro.core.autoscale.autoscale_grid` probes with
    ``simulate_transient`` at the saturation population.

    A closed population this deep pins the bottleneck station near full
    utilization, so the seed-mean makespan rate IS the config's peak
    service rate - the ``lam_peak`` an :class:`~repro.core.api.\
AutoscalePolicy` band is anchored against, only measured on the
    message-level cluster instead of the token simulator."""
    spec = variant_spec(name)
    n_cl = n_clients if n_clients is not None else max(
        8, 2 * spec.executable.n_clients if spec.executable else 8)
    res = run_variant_batched(name, config=config, workload=workload,
                              n_commands=n_commands, seeds=seeds,
                              n_clients=n_cl, **kwargs)
    return float(res.throughput[0].mean())


# ---------------------------------------------------------------------------
# Parity: batched-measured vs analytical (the validate_variant analogue)
# ---------------------------------------------------------------------------


@dataclass
class BatchedParityReport:
    """Measured-vs-analytical msgs/cmd parity for one batched config."""

    variant: str
    config: Config
    model_config: Config
    workload: Workload
    rows: Tuple[StationParity, ...]
    result: BatchedExecutionResult

    @property
    def passed(self) -> bool:
        return all(r.ok for r in self.rows)

    def row(self, station: str) -> StationParity:
        for r in self.rows:
            if r.station == station:
                return r
        raise KeyError(f"no parity row for station {station!r}")

    def max_rel_err(self) -> float:
        return max((r.rel_err for r in self.rows), default=0.0)

    def __str__(self) -> str:
        lines = [f"{self.variant} @ {self.workload.describe()} [batched]: "
                 f"{'PASS' if self.passed else 'FAIL'}"]
        lines += [f"  {r.describe()}" for r in self.rows]
        return "\n".join(lines)


def validate_batched(name: str,
                     config: Optional[Config] = None,
                     workload: Optional[Union[Workload, float]] = None,
                     n_commands: int = 48,
                     seeds: Union[int, Sequence[int]] = 4,
                     **kwargs: Any) -> BatchedParityReport:
    """Parity-check the batched executor's measured per-station msgs/cmd
    against the variant's analytical demand table - the
    :func:`~repro.core.execution.validate_variant` analogue on the
    batched plane, with the same feedback loop: measured-parameter
    refinement comes off a real probe run of this very grid cell."""
    cfg = dict(config) if config is not None else default_config(name)
    cfg.setdefault("variant", name)
    w = resolve_workload(workload, where="validate_batched")
    res = run_variant_batched(name, cfg, w, n_commands=n_commands,
                              seeds=seeds, **kwargs)
    return batched_parity(res, probe_n=n_commands,
                          probe_seed=kwargs.get("probe_seed", 7919))


def batched_parity(res: BatchedExecutionResult, config_index: int = 0,
                   probe_n: Optional[int] = None,
                   probe_seed: int = 7919) -> BatchedParityReport:
    """Measured-vs-analytical msgs/cmd parity of one config of a batched
    run (any row of a :meth:`CompiledSweep.execute` grid).  ``probe_n`` /
    ``probe_seed`` size the feedback probe and should match the run's
    calibration probe (``execute_configs`` defaults ``probe_n`` to
    ``n_commands``)."""
    rows_of = res.shard_lanes(config_index)
    cfg = dict(res.configs[int(rows_of[0])])
    cfg.setdefault("variant", "compartmentalized")
    name = config_variant(cfg)
    spec = variant_spec(name)
    if spec.executable is None:
        raise ValueError(f"variant {name!r} declares no execution plane")
    exe = spec.executable
    w = res.workload
    n_commands = res.n_commands

    model_cfg = spec.adapt(cfg, w)
    if exe.model_feedback is not None:
        # the feedback statistics (skip rates, forwarding fractions) come
        # off a fresh probe run at this config - same loop as the scalar
        # plane, measured not assumed
        probe = run_variant(name, cfg,
                            replace(w, f_write=1.0) if exe.reads_as_writes
                            else w,
                            n_commands=probe_n or n_commands,
                            seed=probe_seed)
        model_cfg = exe.model_feedback(dict(model_cfg), probe)
    if res.geo is not None and res.lane_config is not None:
        # geo runs fan the config into region lanes; parity is against the
        # command-weighted aggregate (regions share the config's costs)
        weights = res.lane_commands[rows_of].astype(float)
        nw = float(res.n_writes[rows_of].sum())
        agg = ((res.station_msgs[rows_of] * weights[:, None]).sum(axis=0)
               / max(weights.sum(), 1.0))
        measured = {STATION_ORDER[j]: float(v)
                    for j, v in enumerate(agg) if v > 0.0}
    else:
        nw = float(res.n_writes[rows_of[0]])
        measured = res.station_row(int(rows_of[0]))
    realized = replace(w, f_write=nw / n_commands)
    predicted = spec.build(model_cfg).demands(realized)

    stations = list(measured)
    stations += [s for s, d in predicted.items()
                 if s not in measured and d > 0.0]
    rows = []
    for station in sorted(stations, key=STATION_ORDER.index):
        mm = measured.get(station, 0.0)
        p = predicted.get(station, 0.0)
        exact = station in exe.exact_stations
        tol = exe.tolerance_for(station)
        rel = abs(mm - p) / max(abs(p), 1e-12)
        ok = abs(mm - p) <= 1e-9 if exact else rel <= tol
        rows.append(StationParity(station=station, measured=mm, predicted=p,
                                  rel_err=rel, tolerance=tol, exact=exact,
                                  ok=ok))
    return BatchedParityReport(variant=name, config=cfg,
                               model_config=model_cfg, workload=w,
                               rows=tuple(rows), result=res)
