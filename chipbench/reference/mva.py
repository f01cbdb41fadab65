"""Exact single-class Mean Value Analysis, as a plain loop.

N closed-loop clients with no think time over stations of per-command
demand ``d`` seconds: the residence time at each station is
``d * (1 + Q)`` with ``Q`` the queue a newcomer finds (the population of
``n - 1``), the throughput is ``n`` over the total residence time, and
Little's law gives the next queue.  Computed in ``dtype``: float64 is the
reference, bfloat16 the control.
"""
from __future__ import annotations

import numpy as np


def mva(demands: np.ndarray, n_max: int, dtype=np.float64):
    """demands: [M, K] seconds.  Returns X[M, n_max] commands/s and
    R[M, n_max] seconds for populations 1..n_max, in ``dtype``."""
    d = np.asarray(demands).astype(dtype)
    one = dtype(1)
    q = np.zeros_like(d)
    xs = np.empty((d.shape[0], n_max), dtype)
    rs = np.empty_like(xs)
    for n in range(1, n_max + 1):
        r_k = (d * (one + q)).astype(dtype)
        r = r_k.sum(axis=1, dtype=dtype)
        x = (dtype(n) / r).astype(dtype)
        xs[:, n - 1], rs[:, n - 1] = x, r
        q = (x[:, None] * r_k).astype(dtype)
    return xs, rs
