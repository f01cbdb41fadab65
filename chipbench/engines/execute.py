"""Engine: ``CompiledSweep.execute``, the batched execution plane.

One answer is one call: probe calibration of every config on the host
cluster, the op-class streams, the execution scan over configs x
``lane_seeds`` seeds of ``clients`` clients draining ``commands`` ops per
lane, the Pallas latency histogram, and the pull of the samples to the
host.  The check runs the plain lane reference over sampled lanes of
sampled answers, and states the paper's message counts of every row.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from chipbench import common
from chipbench.reference import deployments, lanes


class Engine:
    def __init__(self, config: Dict, traffic: Dict, seed: int) -> None:
        from repro.core.sweep import compile_models
        self.config, self.traffic, self.seed = config, traffic, seed
        self.grid = compile_models(
            common.program_models(config),
            [dict(d["knobs"], variant=d["variant"])
             for d in config["deployments"]])
        self.alpha = common.program_alpha(config["alpha_anchor"]["cmd_per_s"])
        self.workload = common.workload(traffic)
        self.lanes = self._lanes()
        self.steps_per_answer = self.lanes["n_steps"]
        self.hist_shape = (len(self.grid) * traffic["lane_seeds"],
                           self.lanes["n_steps"] * traffic["clients"],
                           traffic["bins"])

    def answer(self, index: int):
        t = self.traffic
        seeds = common.lane_seeds(self.seed, index, t["lane_seeds"])
        res = self.grid.execute(
            workload=self.workload, n_commands=t["commands"], seeds=seeds,
            n_clients=t["clients"], alpha=self.alpha, probe_n=t["probe_n"],
            probe_seed=t["probe_seed"], n_bins=t["bins"],
            oversample=t["oversample"], max_steps=t["max_steps"])
        return seeds, res

    @staticmethod
    def keep(out) -> Dict:
        seeds, res = out
        return dict(seeds=seeds, completed=res.completed,
                    n_writes=res.n_writes, throughput=res.throughput,
                    latency_mean=res.latency_mean, hist=res.hist,
                    station_msgs=res.station_msgs, n_steps=res.n_steps)

    def _lanes(self) -> Dict:
        """Every config's per-class demands (s), route, step, edges, and
        the step bound of the whole grid, from the reference's tables."""
        t = self.traffic
        n, n_cl = t["commands"], t["clients"]
        tab_w, tab_r = common.reference_rows(self.config)
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

    def class_stream(self, m: int, lane_seed: int):
        """Exactly ``n_w`` writes, shuffled from the lane's seed, dealt to
        the clients round robin: cls[N, L], budget[N]."""
        t = self.traffic
        n, n_cl = t["commands"], t["clients"]
        n_w = int(self.lanes["n_w"][m])
        flags = np.array([1] * n_w + [0] * (n - n_w), np.int32)
        rng = np.random.default_rng([t["probe_seed"] + m, int(lane_seed)])
        rng.shuffle(flags)
        cls = np.zeros((n_cl, max(-(-n // n_cl), 1)), np.int32)
        budget = np.zeros(n_cl, np.int64)
        for i, flag in enumerate(flags):
            cls[i % n_cl, i // n_cl] = flag
            budget[i % n_cl] += 1
        return cls, budget

    def reference_lane(self, m: int, lane_seed: int, ft=np.float32) -> Dict:
        L = self.lanes
        cls, budget = self.class_stream(m, lane_seed)
        dt = L["dt"][m]
        return lanes.execute_lane(
            common.drain_rates(dt, L["d_w"][m], ft),
            common.drain_rates(dt, L["d_r"][m], ft),
            list(np.nonzero(L["active"][m])[0]), dt, cls, budget,
            L["n_steps"], L["edges"][m], ft=ft)

    def reference_msgs(self, ft=np.float64) -> np.ndarray:
        """Messages per command per server of each row at the realized mix."""
        L, n = self.lanes, self.traffic["commands"]
        n_w = L["n_w"].astype(ft)[:, None]
        tab_w, tab_r = L["tab_w"].astype(ft), L["tab_r"].astype(ft)
        return ((n_w * tab_w + (ft(n) - n_w) * tab_r) / ft(n)).astype(ft)

    def check(self, kept: List[Dict], control=None) -> common.Gaps:
        c, n = self.traffic["check"], self.traffic["commands"]
        gaps = common.Gaps(self.traffic["limits"])
        msgs_ref = self.reference_msgs()
        s_all = self.traffic["lane_seeds"]
        for a in common.sample(self.seed, len(kept), c["answers"], tag=0):
            gaps.answer = a
            got_all = kept[a]
            msgs = (got_all["station_msgs"] if control is None
                    else self.reference_msgs(control))
            gaps.add("messages", common.rel_gap(
                np.asarray(msgs, np.float64), msgs_ref))
            for lane in common.sample(self.seed + a, len(self.grid) * s_all,
                                      c["lanes"], tag=1):
                m, s = divmod(lane, s_all)
                seed_ms = int(got_all["seeds"][s])
                want = self.reference_lane(m, seed_ms)
                done = want["done_w"] + want["done_r"]
                if control is None:
                    got = dict(done=got_all["completed"][m, s],
                               done_w=got_all["n_writes"][m],
                               t_last=n / got_all["throughput"][m, s],
                               latency_mean=got_all["latency_mean"][m, s],
                               hist=got_all["hist"][m, s])
                else:
                    low = self.reference_lane(m, seed_ms, ft=control)
                    low_done = low["done_w"] + low["done_r"]
                    got = dict(done=low_done, done_w=low["done_w"],
                               t_last=low["t_last"],
                               latency_mean=low["lat_sum"] / max(low_done, 1),
                               hist=low["hist"])
                # completions by class over the makespan: every lane
                # drains its budget in any precision, so the counts alone
                # could not tell the control from the engine
                t_want = max(want["t_last"], 1e-30)
                t_got = max(got["t_last"], 1e-30)
                gaps.add("throughput", max(
                    common.rel_gap(got["done"] / t_got, done / t_want),
                    common.rel_gap(got["done_w"] / t_got,
                                   want["done_w"] / t_want)))
                gaps.add("latency_mean", common.rel_gap(
                    got["latency_mean"], want["lat_sum"] / max(done, 1)))
                gaps.add("histogram",
                         common.l1_share(got["hist"], want["hist"]))
        return gaps
