"""JAX performance simulators for the protocol deployments.

Two engines, both deterministic:

* :func:`mva_curve` - exact Mean Value Analysis of the closed queueing
  network induced by a deployment's demand table (N closed-loop clients, one
  outstanding command each - exactly the paper's benchmark setup).  Written
  as a ``jax.lax.scan`` over the client count and ``vmap``-able over
  deployments, so one jitted call sweeps a whole latency-throughput figure
  (paper Fig. 28).

* :func:`fluid_curve` - a slot-stepped fluid simulation of the same network
  (service-rate-limited token buckets per station).  Independent dynamics
  from MVA; used as a cross-check and for transient experiments (e.g. what
  happens when a component is scaled mid-run).

Service demands come from :mod:`repro.core.analytical`; time units are
``1/alpha`` (one message's processing time).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .analytical import DeploymentModel


def demand_vector(model: DeploymentModel, f_write: float = 1.0) -> np.ndarray:
    """Per-station service demand of one command (units of 1/alpha)."""
    return np.array([s.demand(f_write) for s in model.stations], dtype=np.float64)


def _mva_scan_impl(demands: jnp.ndarray, think: jnp.ndarray, n_max: int):
    """Exact single-class MVA.

    demands: [K] per-station demand (already per-server / load-balanced).
    Returns (X[n_max], R[n_max]) for N = 1..n_max.
    """

    def step(q, n):
        with jax.named_scope("mva.step"):
            r_k = demands * (1.0 + q)          # residence time per station
            r = jnp.sum(r_k)
            x = n / (think + r)                # closed-loop throughput
            q_new = x * r_k                    # Little's law per station
        return q_new, (x, r)

    q0 = jnp.zeros_like(demands)
    _, (xs, rs) = jax.lax.scan(step, q0, jnp.arange(1, n_max + 1, dtype=demands.dtype))
    return xs, rs


_mva_scan = partial(jax.jit, static_argnames=("n_max",))(_mva_scan_impl)


@partial(jax.jit, static_argnames=("n_max",))
def _mva_scan_batch(demands: jnp.ndarray, think: jnp.ndarray, n_max: int):
    """Batched MVA: one compiled call over a [M, K] demand matrix.

    Zero-demand columns are inert (they add nothing to residence time), so
    heterogeneous deployments padded to a common K evaluate exactly as their
    unpadded selves.  Returns (X[M, n_max], R[M, n_max]).
    """
    return jax.vmap(lambda d: _mva_scan_impl(d, think, n_max))(demands)


def mva_curve(model: DeploymentModel, alpha: float, n_clients_max: int = 512,
              f_write: float = 1.0, think: float = 0.0
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(clients, throughput cmds/s, median-ish latency seconds) curves."""
    d = jnp.asarray(demand_vector(model, f_write) / alpha)
    xs, rs = _mva_scan(d, jnp.asarray(think), n_clients_max)
    clients = np.arange(1, n_clients_max + 1)
    return clients, np.asarray(xs), np.asarray(rs)


@tracing.span("repro.mva")
def mva_curves_from_demands(demands: np.ndarray, n_clients_max: int = 512,
                            think: float = 0.0
                            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batched MVA straight from a [M, K] demand matrix (units: seconds per
    command per station, i.e. already divided by alpha).  One jitted call
    regardless of M - this is the kernel the sweep engine drives with
    thousands of compiled configs at once.  Returns (clients, X[M, N], R[M, N])."""
    with tracing.span("repro.mva.dispatch"):
        out = _mva_scan_batch(jnp.asarray(demands), jnp.asarray(think),
                              n_clients_max)
    tracing.wait("repro.mva.wait", out[0])
    xs, rs = tracing.pull("repro.mva.pull", *out)
    return np.arange(1, n_clients_max + 1), xs, rs


def _padded_demands(models: Sequence[DeploymentModel], alpha: float,
                    f_write: float) -> np.ndarray:
    """[M, K] demand matrix, padded to the widest station count."""
    ds = [demand_vector(m, f_write) / alpha for m in models]
    k = max(len(d) for d in ds)
    return np.stack([np.pad(d, (0, k - len(d))) for d in ds])


def mva_curves_batch(models: Sequence[DeploymentModel], alpha: float,
                     n_clients_max: int = 512, f_write: float = 1.0
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batched MVA over several deployments (padded to a common station
    count), one jitted call.  Returns (clients, X[m, N], R[m, N])."""
    return mva_curves_from_demands(_padded_demands(models, alpha, f_write),
                                   n_clients_max)


# ---------------------------------------------------------------------------
# Fluid (slot-stepped) simulation
# ---------------------------------------------------------------------------


def _fluid_scan_impl(demands: jnp.ndarray, n_clients: jnp.ndarray,
                     dt: jnp.ndarray, n_steps: int):
    """Pipeline fluid model.

    State: q[K] work queued at each station (in commands), plus a pool of
    clients with one outstanding command each.  Commands flow client ->
    station 0 -> ... -> station K-1 -> client.  Each station drains at rate
    1/demand_k per unit time (aggregate, demand already per-server).
    """
    k = demands.shape[0]

    def step(state, _):
        q, done = state
        # per-station service rate in commands per unit time
        rate = jnp.where(demands > 0, 1.0 / jnp.maximum(demands, 1e-12), jnp.inf)
        served = jnp.minimum(q, rate * dt)
        q = q - served
        # completions at last station return to the client pool and re-enter
        # station 0 instantly (closed loop, zero think time)
        inflow = jnp.concatenate([served[-1:], served[:-1]])
        q = q + inflow
        done = done + served[-1]
        return (q, done), served[-1]

    q0 = jnp.zeros((k,)).at[0].set(n_clients)
    (qf, done), flows = jax.lax.scan(step, (q0, jnp.asarray(0.0)), None,
                                     length=n_steps)
    return done, flows


_fluid_scan = partial(jax.jit, static_argnames=("n_steps",))(_fluid_scan_impl)


@partial(jax.jit, static_argnames=("n_steps",))
def _fluid_scan_batch(demands: jnp.ndarray, n_clients: jnp.ndarray,
                      dt: jnp.ndarray, n_steps: int):
    """Batched fluid pipeline over a [M, K] demand matrix, one compiled call.

    Zero-demand stations serve at effectively infinite rate (see the
    ``jnp.where`` guard in the step), so canonical-slot padding is inert
    here too.  Returns (done[M], flows[M, n_steps])."""
    return jax.vmap(lambda d: _fluid_scan_impl(d, n_clients, dt, n_steps))(demands)


def fluid_throughput(model: DeploymentModel, alpha: float, n_clients: int,
                     f_write: float = 1.0, sim_time: float = 1.0,
                     n_steps: int = 2000) -> float:
    """Steady-state throughput (cmds/s) of the fluid pipeline."""
    d = demand_vector(model, f_write) / alpha
    dt = sim_time / n_steps
    done, flows = _fluid_scan(jnp.asarray(d), jnp.asarray(float(n_clients)),
                              jnp.asarray(dt), n_steps)
    # measure over the second half (post-transient)
    half = n_steps // 2
    return float(np.asarray(flows)[half:].sum() / (dt * (n_steps - half)))


def fluid_throughput_from_demands(demands: np.ndarray, n_clients: int,
                                  sim_time: float = 1.0, n_steps: int = 2000
                                  ) -> np.ndarray:
    """Batched fluid throughput (cmds/s) straight from a [M, K] demand
    matrix (seconds per command per station), one compiled call.
    Returns X[M]."""
    dt = sim_time / n_steps
    _, flows = _fluid_scan_batch(jnp.asarray(demands),
                                 jnp.asarray(float(n_clients)),
                                 jnp.asarray(dt), n_steps)
    half = n_steps // 2
    return np.asarray(flows)[:, half:].sum(axis=1) / (dt * (n_steps - half))


def fluid_throughput_batch(models: Sequence[DeploymentModel], alpha: float,
                           n_clients: int, f_write: float = 1.0,
                           sim_time: float = 1.0, n_steps: int = 2000
                           ) -> np.ndarray:
    """Steady-state fluid throughput (cmds/s) of several deployments in one
    compiled call.  Returns X[M]."""
    return fluid_throughput_from_demands(
        _padded_demands(models, alpha, f_write), n_clients, sim_time, n_steps)


# ---------------------------------------------------------------------------
# Discrete-event cross-validation (numpy; exact FIFO multi-server queues)
# ---------------------------------------------------------------------------


def des_throughput(model: DeploymentModel, alpha: float, n_clients: int,
                   f_write: float = 1.0, n_commands: int = 20_000,
                   seed: int = 0, deterministic_service: bool = True,
                   warmup_commands: Optional[int] = None
                   ) -> Tuple[float, float]:
    """Event-driven simulation of the closed network.  Returns
    (throughput cmds/s, mean latency s), both measured over a post-warmup
    window (the first ``warmup_commands`` completions - default 10% - are
    discarded, so the cold-start ramp where all N clients burst into
    station 0 at t=0 doesn't bias the steady-state estimate this function
    cross-validates against MVA/fluid and the transient engine)."""
    import heapq

    rng = np.random.default_rng(seed)
    if warmup_commands is None:
        warmup_commands = n_commands // 10
    demands = demand_vector(model, f_write) / alpha  # seconds per station
    k = len(demands)
    servers = np.array([s.servers for s in model.stations])
    # each station: per-server demand d means one server finishes a command
    # in d*servers... demands are already per-server shares of the command;
    # total work per command at station = d * servers, split across servers.
    work = demands * servers

    free_at = [np.zeros(s) for s in servers]  # next-free time per server
    events: List[Tuple[float, int, int, int]] = []  # (time, seq, cmd, stage)
    seq = 0
    for c in range(n_clients):
        heapq.heappush(events, (0.0, seq, c, 0))
        seq += 1
    start = np.zeros(n_clients)
    done = 0
    measured = 0
    total_latency = 0.0
    t = 0.0
    t_warm = 0.0
    while done < n_commands and events:
        t, _, cmd, stage = heapq.heappop(events)
        if stage == 0:
            start[cmd] = t
        if stage == k:
            done += 1
            if done <= warmup_commands:
                t_warm = t
            else:
                measured += 1
                total_latency += t - start[cmd]
            heapq.heappush(events, (t, seq, cmd, 0))
            seq += 1
            continue
        svc = work[stage]
        if not deterministic_service:
            svc = rng.exponential(svc)
        i = int(np.argmin(free_at[stage]))
        begin = max(t, free_at[stage][i])
        finish = begin + svc
        free_at[stage][i] = finish
        heapq.heappush(events, (finish, seq, cmd, stage + 1))
        seq += 1
    throughput = measured / (t - t_warm) if t > t_warm else 0.0
    return throughput, total_latency / max(measured, 1)
