"""Engine: ``CompiledSweep.transient``, the batched token scan.

One answer is one call: every deployment of the configuration x
``lane_seeds`` seeds, ``clients`` closed-loop clients per lane, ``steps``
scan steps.  The check runs the plain lane reference over sampled lanes
of sampled answers, on the very service draws of those lanes.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np

from chipbench import common
from chipbench.reference import lanes



class Engine:
    def __init__(self, config: Dict, traffic: Dict, seed: int) -> None:
        from repro.core.sweep import compile_models
        self.config, self.traffic, self.seed = config, traffic, seed
        self.grid = compile_models(common.program_models(config))
        self.alpha = common.program_alpha(config["alpha_anchor"]["cmd_per_s"])
        self.workload = common.workload(traffic)
        self.steps_per_answer = traffic["steps"]

    def answer(self, index: int):
        t = self.traffic
        seeds = common.lane_seeds(self.seed, index, t["lane_seeds"])
        res = self.grid.transient(
            self.alpha, n_clients=t["clients"], workload=self.workload,
            seeds=seeds, n_steps=t["steps"],
            exponential_service=t["service"] == "exponential",
            warmup_frac=t["warmup_fraction"], oversample=t["oversample"],
            n_bins=t["bins"])
        return seeds, res

    @staticmethod
    def keep(out) -> Dict:
        seeds, res = out
        return dict(seeds=seeds, flows=res.flows, done=res.completed,
                    latency_mean=res.latency_mean, hist=res.hist,
                    qsum=res.queue_sums)

    def _lane_inputs(self):
        """Demands [1, M, K] s, dt [M], bin edges [M, B + 1], warm-up."""
        t = self.traffic
        d_w, d_r = common.reference_rows(self.config)
        d = (common.ref_deployments.blend(d_w, d_r, common.f_write(t))
             / common.reference_alpha(self.config))[None]
        active = d.max(axis=0) > 0
        dt = d.max(axis=2).min(axis=0) / t["oversample"]
        rtt = np.maximum((d * active[None]).sum(axis=2).min(axis=0), 1e-12)
        edges = common.log_edges(rtt, t["steps"] * dt, t["bins"])
        return d, dt, edges, int(t["steps"] * t["warmup_fraction"])

    def reference_lane(self, m: int, lane_seed: int, ft=np.float32) -> Dict:
        t = self.traffic
        d, dt, edges, warmup = self._lane_inputs()
        key = jax.random.fold_in(jax.random.key(0), int(lane_seed))
        if t["service"] == "exponential":
            draws = jax.random.exponential(key, (t["steps"] + 1, d.shape[2]))
        else:
            draws = np.ones((t["steps"] + 1, d.shape[2]), np.float32)
        return lanes.transient_lane(
            d[:, m], common.drain_rates(dt[m], d[:, m], ft), [0], dt[m],
            np.asarray(draws), t["clients"], t["steps"], warmup, edges[m],
            ft=ft)

    def check(self, kept: List[Dict], control=None) -> common.Gaps:
        """Compare sampled lanes with the reference.  With ``control`` (a
        floating type), that type's reference stands in for the engine."""
        c = self.traffic["check"]
        gaps = common.Gaps(self.traffic["limits"])
        m_all, s_all = len(self.grid), self.traffic["lane_seeds"]
        for a in common.sample(self.seed, len(kept), c["answers"], tag=0):
            gaps.answer = a
            got_all = kept[a]
            for lane in common.sample(self.seed + a, m_all * s_all,
                                      c["lanes"], tag=1):
                m, s = divmod(lane, s_all)
                seed_ms = int(got_all["seeds"][s])
                want = self.reference_lane(m, seed_ms)
                if control is None:
                    got = dict(flows=got_all["flows"][m, s],
                               done=got_all["done"][m, s],
                               latency_mean=got_all["latency_mean"][m, s],
                               hist=got_all["hist"][m, s],
                               qsum=got_all["qsum"][m, s])
                else:
                    low = self.reference_lane(m, seed_ms, ft=control)
                    got = dict(low, latency_mean=low["lat_sum"]
                               / max(low["done"], 1))
                l1, rel = common.l1_share, common.rel_gap
                gaps.add("completions", max(l1(got["flows"], want["flows"]),
                                            rel(got["done"], want["done"])))
                gaps.add("latency_mean", rel(
                    got["latency_mean"], want["lat_sum"] / max(want["done"], 1)))
                gaps.add("histogram", l1(got["hist"], want["hist"]))
                gaps.add("queue_sums", l1(got["qsum"], want["qsum"]))
        return gaps
