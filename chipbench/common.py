"""What every engine of the benchmark shares: seeds, sampling, the mix,
the deployments as the system under test builds them, and the gaps.

Inputs come from ``--seed`` and the index of the answer alone, so the
same seed asks the same questions, and no two answers of a run ask the
same one.  Every seed asks questions of the same size.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.reference import deployments as ref_deployments


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *tags])


def lane_seeds(seed: int, answer: int, n: int) -> np.ndarray:
    """``n`` distinct int32 lane seeds of one answer."""
    r = rng(seed, 1, answer)
    return r.choice(2**31 - 1, size=n, replace=False).astype(np.int32)


def sample(seed: int, n: int, k: int, tag: int) -> List[int]:
    """``k`` of ``range(n)`` (all when ``k >= n``), drawn from the seed."""
    if k >= n:
        return list(range(n))
    return sorted(int(i) for i in rng(seed, 2, tag).choice(n, k, replace=False))


def f_write(traffic: Dict) -> float:
    """Write fraction of the mix, as ``Workload.read_mix`` forms it."""
    return 1.0 - traffic["mix"]["read_fraction"]


def workload(traffic: Dict):
    from repro.core.api import Workload
    return Workload.read_mix(traffic["mix"]["read_fraction"])


def program_models(config: Dict):
    """The configuration's deployments through the system's own model
    constructors (``<variant>_model`` of ``repro.core.analytical``)."""
    from repro.core import analytical
    return [getattr(analytical, f"{d['variant']}_model")(**d["knobs"])
            for d in config["deployments"]]


def program_alpha(anchor_cmd_per_s: float) -> float:
    from repro.core.analytical import calibrate_alpha
    return calibrate_alpha(anchor_cmd_per_s)


def reference_rows(config: Dict):
    """The reference's write/read demand rows, in messages."""
    return ref_deployments.demand_rows(config["deployments"],
                                       config["station_columns"])


def reference_alpha(config: Dict, anchor_cmd_per_s: float = None) -> float:
    anchor = dict(config["alpha_anchor"])
    if anchor_cmd_per_s is not None:
        anchor["cmd_per_s"] = anchor_cmd_per_s
    return ref_deployments.alpha(anchor)


def log_edges(rtt: np.ndarray, horizon: np.ndarray, n_bins: int) -> np.ndarray:
    """Log-spaced latency bin edges per row: from half the zero-load round
    trip up to the simulated horizon."""
    lo = rtt * 0.5
    hi = np.maximum(horizon, lo * 10.0)
    ratio = (hi / lo) ** (1.0 / n_bins)
    return lo[:, None] * ratio[:, None] ** np.arange(n_bins + 1)[None, :]


_DEVICE_DIV = None


def drain_rates(dt: float, d: np.ndarray, ft=np.float32) -> np.ndarray:
    """Work a station drains per step, ``dt / d`` (a zero demand drains in
    one step), in ``ft``.  Float32 quotients are taken on JAX's default
    device, where the engines divide: the TPU's float32 division is not
    correctly rounded (1.2e-6 / 6e-6 gives 0.20000002, not 0.2), and a
    quotient one unit off moves a completion by a step."""
    global _DEVICE_DIV
    if ft is np.float32:
        import jax
        import jax.numpy as jnp
        if _DEVICE_DIV is None:
            _DEVICE_DIV = jax.jit(lambda a, b: jnp.where(
                b > 0, a / jnp.maximum(b, 1e-30), 1e30))
        return np.asarray(_DEVICE_DIV(np.float32(dt),
                                      np.asarray(d, np.float32)))
    d = np.asarray(d).astype(ft)
    return np.where(d > 0, ft(dt) / np.maximum(d, ft(1e-30)),
                    ft(1e30)).astype(ft)


def rel_gap(got, want) -> float:
    """Largest relative gap, measured against the reference's magnitude."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-300)))


def l1_share(got, want) -> float:
    """Total absolute gap as a share of the reference's total."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    return float(np.abs(got - want).sum() / max(np.abs(want).sum(), 1.0))


class Gaps:
    """Worst gap per compared number, and the limit each is held to."""

    def __init__(self, limits: Dict[str, float]) -> None:
        self.limits = dict(limits)
        self.worst = {name: 0.0 for name in limits}
        self.failed = set()     # answers with a gap over its limit
        self.answer = None

    def add(self, name: str, value: float) -> None:
        if not np.isfinite(value):
            value = float("inf")
        self.worst[name] = max(self.worst[name], float(value))
        if value > self.limits[name]:
            self.failed.add(self.answer)

    def rows(self) -> List[Dict]:
        return [{"name": n, "value": self.worst[n], "limit": self.limits[n]}
                for n in self.limits]

