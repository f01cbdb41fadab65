"""The token-service step both device scans run, written once.

A lane walks closed-loop clients through a tandem of FIFO stations.  Its
tokens are, per client, the station ``stage``, the place ``rank`` in that
station's queue and the time ``enter_t`` its command entered; per station,
the queue length ``q`` and the head's remaining ``work`` in services.
Leaving the ring is data, not a mode: a client whose completing command
is its ``last`` takes rank -1, which is never a queue's head, so it never
moves again.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def routing(active: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Tandem routing over the active stations of each row of active
    [M, K]: (entry[M], next_station[M, K]), where ``K`` marks command
    completion (inactive stations point to K too; they host nothing)."""
    m, k = active.shape
    entry = np.zeros(m, dtype=np.int32)
    nxt = np.full((m, k), k, dtype=np.int32)
    for i in range(m):
        idx = np.nonzero(active[i])[0]
        if idx.size == 0:
            raise ValueError(f"row {i} has no active station")
        entry[i] = idx[0]
        nxt[i, idx[:-1]] = idx[1:]
    return entry, nxt


def start(entry, nxt, key, alive, n_steps: int, exponential: bool):
    """A lane's ring, first tokens and unit service draws [n_steps, K]
    (exponential, or ones), one row a step.  entry: scalar; nxt: [K];
    alive: [N] bool, the clients queued at the entry in client order (the
    rest start parked, at rank -1).  The ring is (entry, finishes_at[K]: a
    departure there completes the command, arrive_at[K]: where the client
    goes next, the entry after a completion)."""
    k = nxt.shape[0]
    if exponential:
        draws = jax.random.exponential(key, (n_steps + 1, k))
    else:
        draws = jnp.ones((n_steps + 1, k))
    finishes_at = nxt == k
    ring = (entry, finishes_at, jnp.where(finishes_at, entry, nxt))
    queued = alive.astype(jnp.int32)
    tokens = (jnp.full(alive.shape, entry, jnp.int32),
              jnp.where(alive, jnp.cumsum(queued) - 1, -1),
              jnp.zeros(alive.shape),
              jnp.zeros((k,), jnp.int32).at[entry].add(jnp.sum(queued)),
              jnp.zeros((k,)).at[entry].set(draws[0, entry]))
    return ring, tokens, draws[1:]


def drain_rate(d_now, dt):
    """Work drained per step under demands ``d_now`` [K] seconds.  A zero
    demand ("free" service) drains at once rather than stall - still at
    most one completion per step, i.e. 1/dt per station."""
    return jnp.where(d_now > 0, dt / jnp.maximum(d_now, 1e-30), 1e30)


def step(tokens, rate, draw, t_end, last, ring):
    """Drain every busy station by ``rate`` [K], move each departing head
    one hop (or park it, if its command completes and is its ``last``),
    and start the next services from ``draw`` [K].  ``t_end`` ends the
    step.  Returns (tokens, fin[N], lat[N]): the clients whose command
    completed, and every client's time since its command entered."""
    stage, rank, enter_t, q, work = tokens
    entry, finishes_at, arrive_at = ring
    k = q.shape[0]
    with jax.named_scope("token.drain"):
        busy = q > 0
        work = jnp.where(busy, work - rate, work)
        complete = busy & (work <= 0.0)                        # [K]
        dep_here = complete[stage]                             # [N]
        moving = dep_here & (rank == 0)
        fin = moving & finishes_at[stage]                      # command done
        lat = t_end - enter_t

    with jax.named_scope("token.route"):
        parks = fin & last
        dest = arrive_at[stage]                                # [N]
        q_dep = q - complete.astype(q.dtype)
        stage_new = jnp.where(moving, dest, stage)
        enter_new = jnp.where(fin, t_end, enter_t)
        rank_new = jnp.where(
            moving, jnp.where(parks, -1, q_dep[dest]),
            rank - (dep_here & (rank > 0)).astype(rank.dtype))
        # each completing station sends its head on; a parked one does not
        # arrive at the entry
        arrivals = (jnp.zeros_like(q)
                    .at[arrive_at].add(complete.astype(q.dtype))
                    - jnp.where(jnp.arange(k) == entry,
                                jnp.sum(parks, dtype=q.dtype), 0))
        q_new = q_dep + arrivals
        # new head enters service: carry the completion residual on a busy
        # server (unbiased long-run rate), fresh draw on an idle one
        fresh = (complete & (q_new > 0)) | (~busy & (arrivals > 0))
        work_new = jnp.where(
            fresh, draw + jnp.where(complete, work, 0.0), work)
    return (stage_new, rank_new, enter_new, q_new, work_new), fin, lat


def bin_edges(rtt, n_steps: int, dt, n_bins: int) -> np.ndarray:
    """Log-spaced latency bins [M, n_bins + 1] from half the zero-load
    round trip ``rtt`` [M] to the horizon ``n_steps * dt`` [M]."""
    lo = np.maximum(rtt, 1e-12) * 0.5
    hi = np.maximum(n_steps * dt, lo * 10.0)
    ratio = (hi / lo) ** (1.0 / n_bins)
    return lo[:, None] * ratio[:, None] ** np.arange(n_bins + 1)[None, :]


def quantile_from_hist(hist: np.ndarray, edges: np.ndarray, q: float
                       ) -> np.ndarray:
    """hist: [M, S, B]; edges: [M, B+1] (log-spaced).  Returns [M, S]
    latency at quantile q, log-interpolated inside the landing bin."""
    cum = hist.cumsum(axis=2)
    total = np.maximum(cum[:, :, -1], 1)
    target = q * total
    idx = np.minimum((cum < target[:, :, None]).sum(axis=2),
                     hist.shape[2] - 1)
    lo = np.take_along_axis(np.broadcast_to(edges[:, None, :-1], hist.shape),
                            idx[:, :, None], axis=2)[:, :, 0]
    hi = np.take_along_axis(np.broadcast_to(edges[:, None, 1:], hist.shape),
                            idx[:, :, None], axis=2)[:, :, 0]
    below = np.where(idx > 0,
                     np.take_along_axis(cum, np.maximum(idx - 1, 0)[:, :, None],
                                        axis=2)[:, :, 0], 0)
    inbin = np.maximum(
        np.take_along_axis(hist, idx[:, :, None], axis=2)[:, :, 0], 1)
    frac = np.clip((target - below) / inbin, 0.0, 1.0)
    return lo * (hi / lo) ** frac
