"""Least time of a kernel on a chip, from its shapes and the chip's peaks.

The least time is the larger of the operations over the peak operation
rate and the bytes over the peak memory bandwidth; the roofline share is
that over the kernel's measured device time.  It cannot pass 100% unless
the count is too high or the time leaves out part of the work.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple


def latency_hist_cost(lanes: int, samples: int, bins: int
                      ) -> Tuple[float, float]:
    """Operations and bytes the masked histogram needs for ``lanes`` rows
    of ``samples`` latencies each, binned against ``bins + 1`` edges.

    Bytes: each sample read once (float32, 4 B) with its valid flag (bool,
    1 B, as the engine hands it over), each lane's edges read once
    (float32), each lane's counts written once (int32).  Operations: one
    binary search per sample, ``ceil(log2(bins + 1))`` comparisons."""
    n = float(lanes) * float(samples)
    bytes_ = n * (4 + 1) + lanes * (bins + 1) * 4.0 + lanes * bins * 4.0
    ops = n * math.ceil(math.log2(bins + 1))
    return ops, bytes_


def share(ops: float, bytes_: float, seconds: float, peak: Dict
          ) -> Tuple[float, str]:
    """(roofline share in %, the bound that applies: "ops" or "bytes")."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "ops"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
