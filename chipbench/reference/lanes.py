"""Plain references of the two scan engines: one closed-loop lane at a time.

A lane is N clients, one outstanding command each, walking the active
stations of one deployment in column order and starting over when the
last station finishes their command.  Every station is a FIFO queue that
drains the work of the command at its head by ``dt / d`` per step of
``dt`` seconds (``d`` is the station's demand in seconds, for the class of
that command); a command is done at a station in the step its work falls
to zero or below, and the next command starts with that residual carried
over.  A station finishes at most one command a step.

These are written as explicit queues of client ids, one Python step at a
time, and import nothing of the system under test.  ``ft`` is the
floating type every float is rounded to after each operation: float32 is
the precision the configurations state, so a lane computed here follows
the same sample path as the engine; bfloat16 is the control.  The drain
per step, ``dt / d``, comes in from the caller (``common.drain_rates``),
which divides where the engine does.
"""
from __future__ import annotations

import bisect
from collections import deque
from typing import Dict, List, Sequence

import numpy as np


def _ring(active: Sequence[int]) -> Dict[int, int]:
    return {k: active[(j + 1) % len(active)] for j, k in enumerate(active)}


def _binner(edges, ft):
    """Bin of a latency: edges strictly below it, less one, clamped."""
    e = [float(ft(x)) for x in edges]
    n_bins = len(e) - 1
    return lambda lat: min(max(bisect.bisect_left(e, float(lat)) - 1, 0),
                           n_bins - 1)


def transient_lane(demands: np.ndarray, rates: np.ndarray,
                   bounds: Sequence[int], dt: float, draws: np.ndarray,
                   n_clients: int, n_steps: int, warmup: int,
                   edges: np.ndarray, ft=np.float32) -> Dict:
    """One lane of the token scan with piecewise-constant demands.

    demands: [W, K] seconds, window ``w`` holding from step ``bounds[w]``;
    rates: [W, K] work drained per step; draws: [n_steps + 1, K] service
    draws in work units (row 0 starts the first service, row i + 1 any
    service that starts in step i); edges: [B + 1] latency bin edges.
    Completions in steps >= ``warmup`` are recorded.  Returns
    flows[n_steps] (all completions per step), done, lat_sum, hist[B] and
    qsum[W, K] (queue lengths summed per window)."""
    n_win, k_all = demands.shape
    active = [k for k in range(k_all) if demands[:, k].max() > 0]
    entry, last = active[0], active[-1]
    nxt = _ring(active)
    dt_ = ft(dt)
    rates = [{k: ft(rates[w, k]) for k in active} for w in range(n_win)]
    to_bin = _binner(edges, ft)

    queue = {k: deque() for k in active}
    queue[entry].extend(range(n_clients))
    work = {k: ft(0) for k in active}
    work[entry] = ft(draws[0, entry])
    enter: List = [ft(0)] * n_clients
    flows = np.zeros(n_steps, np.int64)
    hist = np.zeros(len(edges) - 1, np.int64)
    qsum = [[ft(0)] * k_all for _ in range(n_win)]
    done, lat_sum, w = 0, ft(0), 0
    for i in range(n_steps):
        while w + 1 < n_win and bounds[w + 1] <= i:
            w += 1
        t_end = ft(i + 1) * dt_
        busy = {k: bool(queue[k]) for k in active}
        finished = []
        for k in active:
            if busy[k]:
                work[k] = work[k] - rates[w][k]
                if work[k] <= 0:
                    finished.append(k)
        movers = [(k, queue[k].popleft()) for k in finished]
        arrived = set()
        for k, c in movers:
            if k == last:
                flows[i] += 1
                if i >= warmup:
                    lat = t_end - enter[c]
                    done += 1
                    lat_sum = lat_sum + lat
                    hist[to_bin(lat)] += 1
                enter[c] = t_end
            queue[nxt[k]].append(c)
            arrived.add(nxt[k])
        row = qsum[w]
        for k in active:
            row[k] = row[k] + ft(len(queue[k]))
            if k in finished and queue[k]:
                work[k] = ft(draws[i + 1, k]) + work[k]
            elif not busy[k] and k in arrived:
                work[k] = ft(draws[i + 1, k])
    return dict(flows=flows, done=done, lat_sum=float(lat_sum), hist=hist,
                qsum=np.asarray(qsum, dtype=np.float64))


def execute_lane(rate_w: np.ndarray, rate_r: np.ndarray,
                 active: Sequence[int], dt: float, cls: np.ndarray,
                 budget: np.ndarray, n_steps: int, edges: np.ndarray,
                 ft=np.float32) -> Dict:
    """One lane of the execution scan: deterministic service, op budgets.

    rate_w/rate_r: [K] work drained per step by a write / a read at the
    head of a station; active: station columns in visit order; cls:
    [N, L] op classes per client (1 = write, 0 = read); budget: [N] ops
    per client.  A client
    whose budget is spent parks.  Every completion is a latency sample.
    Returns done_w, done_r, t_last, lat_sum (float64), hist[B]."""
    entry, last = active[0], active[-1]
    nxt = _ring(active)
    dt_ = ft(dt)
    rate_w = {k: ft(rate_w[k]) for k in active}
    rate_r = {k: ft(rate_r[k]) for k in active}
    to_bin = _binner(edges, ft)
    n_clients, n_ops = cls.shape
    cls = cls.tolist()

    queue = {k: deque() for k in active}
    queue[entry].extend(c for c in range(n_clients) if budget[c] > 0)
    work = {k: ft(0) for k in active}
    work[entry] = ft(1.0)
    enter: List = [ft(0)] * n_clients
    op = [0] * n_clients
    hist = np.zeros(len(edges) - 1, np.int64)
    done_w = done_r = 0
    lat_sum, t_last = 0.0, ft(0)
    for i in range(n_steps):
        t_end = ft(i + 1) * dt_
        busy = {k: bool(queue[k]) for k in active}
        finished = []
        for k in active:
            if busy[k]:
                head = queue[k][0]
                write = cls[head][min(op[head], n_ops - 1)] > 0
                work[k] = work[k] - (rate_w[k] if write else rate_r[k])
                if work[k] <= 0:
                    finished.append(k)
        movers = [(k, queue[k].popleft()) for k in finished]
        arrived = set()
        for k, c in movers:
            if k == last:
                lat = t_end - enter[c]
                if cls[c][min(op[c], n_ops - 1)] > 0:
                    done_w += 1
                else:
                    done_r += 1
                t_last = t_end
                lat_sum += float(lat)
                hist[to_bin(lat)] += 1
                enter[c] = t_end
                op[c] += 1
                if op[c] >= budget[c]:
                    continue
            queue[nxt[k]].append(c)
            arrived.add(nxt[k])
        for k in active:
            if k in finished and queue[k]:
                work[k] = ft(1.0) + work[k]
            elif not busy[k] and k in arrived:
                work[k] = ft(1.0)
    return dict(done_w=done_w, done_r=done_r, t_last=float(t_last),
                lat_sum=lat_sum, hist=hist)
