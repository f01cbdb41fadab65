"""Engine: ``CompiledSweep.execute`` on deployments whose acceptors may
use majority quorums, such as the paper's Fig. 29a ablation.

The execute engine (``chipbench/engines/execute.py``) with the plain
reference's demand rows taken from ``chipbench.reference.majority``, which
prices the 2f+1 majority column besides all that ``deployments`` prices.
The program answers under the same root span, ``repro.execute``, so the
engine presents its traffic mix to the span readers as ``execute``.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

from chipbench import common
from chipbench.engines import execute
from chipbench.reference import deployments, majority


class Engine(execute.Engine):
    def __init__(self, config: Dict, traffic: Dict, seed: int) -> None:
        super().__init__(config, dict(traffic, engine="execute"), seed)

    def _lanes(self) -> Dict:
        """As ``execute.Engine._lanes``, from ``majority``'s rows."""
        t = self.traffic
        n, n_cl = t["commands"], t["clients"]
        tab_w, tab_r = majority.demand_rows(self.config["deployments"],
                                            self.config["station_columns"])
        a = common.reference_alpha(self.config)
        d_w, d_r = tab_w / a, tab_r / a
        f = np.array([1.0 if d["variant"] in deployments.READS_AS_WRITES
                      else common.f_write(t)
                      for d in self.config["deployments"]])
        n_w = np.array([round(n * fi) for fi in f])
        active = (((n_w > 0)[:, None] & (d_w > 0))
                  | ((n_w < n)[:, None] & (d_r > 0)))
        blend = f[:, None] * d_w + (1.0 - f[:, None]) * d_r
        dt = blend.max(axis=1) / t["oversample"]
        d_hot = np.where(active, np.maximum(d_w, d_r), 0.0)
        steps = ((n + n_cl) * d_hot.sum(axis=1) / dt
                 + (n + n_cl) * active.sum(axis=1))
        n_steps = int(math.ceil(1.3 * float(steps.max()))) + 8
        n_steps = -(-n_steps // 256) * 256
        rtt = np.maximum((blend * active).sum(axis=1), 1e-12)
        edges = common.log_edges(rtt, n_steps * dt, t["bins"])
        return dict(d_w=d_w, d_r=d_r, tab_w=tab_w, tab_r=tab_r, n_w=n_w,
                    active=active, dt=dt, n_steps=n_steps, edges=edges)
