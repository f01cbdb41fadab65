"""Engine: ``CompiledSweep.mva``, the batched exact-MVA solve.

One answer is the whole latency-throughput surface of the configuration's
deployments for populations 1..``clients``, under a per-message cost
anchored at a throughput drawn from the seed and the answer's index
(``anchor_spread`` around the configuration's anchor): the question a
sizing tool asks over and over, one calibration at a time.  The check
runs the float64 recursion over every row and population of sampled
answers.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import common
from chipbench.reference import deployments, mva



class Engine:
    def __init__(self, config: Dict, traffic: Dict, seed: int) -> None:
        from repro.core.sweep import compile_models
        self.config, self.traffic, self.seed = config, traffic, seed
        self.grid = compile_models(common.program_models(config))
        self.workload = common.workload(traffic)
        self.steps_per_answer = traffic["clients"]

    def anchor(self, index: int) -> float:
        lo, hi = self.traffic["anchor_spread"]
        u = common.rng(self.seed, 3, index).uniform(lo, hi)
        return self.config["alpha_anchor"]["cmd_per_s"] * u

    def answer(self, index: int):
        anchor = self.anchor(index)
        _, x, r = self.grid.mva(common.program_alpha(anchor),
                                n_clients_max=self.traffic["clients"],
                                workload=self.workload)
        return anchor, x, r

    @staticmethod
    def keep(out) -> Dict:
        anchor, x, r = out
        return dict(anchor=anchor, x=x, r=r)

    def reference(self, anchor: float, dtype=np.float64):
        d_w, d_r = common.reference_rows(self.config)
        d = (deployments.blend(d_w, d_r, common.f_write(self.traffic))
             / common.reference_alpha(self.config, anchor))
        return mva.mva(d, self.traffic["clients"], dtype)

    def check(self, kept: List[Dict], control=None) -> common.Gaps:
        gaps = common.Gaps(self.traffic["limits"])
        for a in common.sample(self.seed, len(kept),
                               self.traffic["check"]["answers"], tag=0):
            gaps.answer = a
            got = kept[a]
            x_ref, r_ref = self.reference(got["anchor"])
            if control is not None:
                x_got, r_got = self.reference(got["anchor"], control)
            else:
                x_got, r_got = got["x"], got["r"]
            gaps.add("throughput", common.rel_gap(x_got, x_ref))
            gaps.add("residence_time", common.rel_gap(r_got, r_ref))
        return gaps
