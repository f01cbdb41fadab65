"""Elastic autoscaling: the closed loop from measured load to live resize.

Every other benchmark picks one *static* configuration and holds it;
production traffic breathes.  This module drives the autoscale
controller (``repro.core.autoscale``) through the two canonical arrival
shapes and reports the headline the subsystem exists for:

* the diurnal policy search: a policy grid (plus the frozen static-peak
  baseline) closed-loop over one day of sharpened-cosine load, every
  lane's full-horizon replay in ONE jitted device call
  (``autotune_policy`` / ``autoscale_grid``) - the winner must hold
  equal-or-better worst-window p99 than static-peak provisioning while
  saving >= 25% machine-hours;
* the flash crowd: a controller that had drained to the trough floor
  re-provisions the pipeline inside the crowd plateau, machine budget
  respected;
* the (config x policy) grid through ``CompiledSweep.autoscale`` - the
  policy-search shape, config-major lanes;
* the execution-plane replay: ``run_autoscaled`` re-enacts the emitted
  plan on a real registered-variant cluster - linearizable across every
  resize, warm-phase dips parity-checked against the transient
  prediction (the acceptance gate);
* the capacity anchor: ``measured_capacity`` (batched executor) - the
  execution-plane twin of the transient probe the controller calibrates
  utilization against.

Emits ``BENCH_autoscale.json`` (machine-hours and p99, autoscaled vs
static-peak) - the machine-readable perf anchor; the smoke run
(``BENCH_SMOKE=1``, set by ``make autoscale-smoke``) writes it under
``results/`` instead so the committed anchor stays the full run's.
"""
import json
import os
import time
from pathlib import Path

import numpy as np

from repro.core import (
    AutoscalePolicy,
    Controller,
    SweepSpec,
    Workload,
    autotune_policy,
    calibrate_alpha,
    compile_sweep,
    diurnal_load,
    flash_crowd_load,
    measured_capacity,
    resizable_stations,
    run_autoscaled,
)
from repro.core.api import STATION_ORDER
from repro.core.sweep import model_for

SMOKE = os.environ.get("BENCH_SMOKE", "") not in ("", "0")
#: (diurnal windows, replay steps, seeds) of the full run
FULL_SIZE = (32, 4800, 3)
W_DIURNAL, N_STEPS, SEEDS = (20, 3000, 2) if SMOKE else FULL_SIZE

# the deployment being autoscaled: a peak-provisioned compartmentalized
# pipeline with every independently-scalable tier populated
CFG = {"variant": "compartmentalized", "f": 1, "n_proxy_leaders": 8,
       "grid_rows": 2, "grid_cols": 2, "n_replicas": 6,
       "n_batchers": 3, "n_unbatchers": 3}
# floors keep the drained pipeline's latency floor (sum of per-server
# demands) under the static peak p99 - the "equal p99" budget
FLOORS = (("proxy", 3), ("replica", 2), ("batcher", 2), ("unbatcher", 2))
# the diurnal policy grid (the frozen static-peak baseline is added by
# autotune_policy)
DIURNAL_POLICIES = (
    AutoscalePolicy(target_low=0.4, target_high=0.65,
                    cooldown_windows=0, min_counts=FLOORS),
    AutoscalePolicy(target_low=0.35, target_high=0.6,
                    cooldown_windows=0, min_counts=FLOORS),
    AutoscalePolicy(target_low=0.4, target_high=0.65,
                    cooldown_windows=0, min_counts=FLOORS,
                    queue_high=1.0),
)


def demand_row(cfg, w, alpha):
    m = model_for(dict(cfg), w)
    d_w, d_r, servers = m.demand_slots()
    k = len(STATION_ORDER)
    row = (w.f_write * np.asarray(d_w[:k], dtype=np.float64)
           + (1.0 - w.f_write) * np.asarray(d_r[:k], dtype=np.float64))
    return row / alpha, np.asarray(servers[:k], dtype=np.int64)


def run(alpha=None):
    alpha = alpha if alpha is not None else calibrate_alpha()
    rows = []
    w = Workload(f_write=1.0)
    base, srv = demand_row(CFG, w, alpha)
    rz = resizable_stations("compartmentalized", CFG)
    static_machines = int(srv.sum())

    # -- headline: diurnal policy search, autoscaled vs static-peak --------
    load = diurnal_load(W_DIURNAL, low=0.15, sharpness=2.0)
    policies = DIURNAL_POLICIES
    t0 = time.perf_counter()
    tune = autotune_policy(policies, base, srv, load, p99_slack=1.0,
                           seeds=SEEDS, n_steps=N_STEPS,
                           resizable=[rz] * (len(policies) + 1))
    us = (time.perf_counter() - t0) * 1e6
    saved = 1.0 - tune.winner.machine_time / tune.static.machine_time
    assert tune.winner.policy is not None, "no policy beat static-peak"
    assert saved >= 0.25, f"only {saved:.0%} machine-hours saved"
    assert tune.winner.peak_p99 <= tune.static.peak_p99, (
        tune.winner.peak_p99, tune.static.peak_p99)
    rows.append((f"autoscale/diurnal_policy_search_{len(policies) + 1}"
                 f"x{W_DIURNAL}", us,
                 f"{tune.describe()}; {len(tune.winner.trace.actions)} "
                 f"resizes, trough floor "
                 f"{int(tune.winner.trace.machines.min())} of "
                 f"{static_machines} machines"))

    # -- flash crowd: drained floor -> crowd -> re-provisioned -------------
    crowd = flash_crowd_load(16 if not SMOKE else 12, base=0.25,
                             start=0.45, width=0.3)
    pol = AutoscalePolicy(target_low=0.4, target_high=0.65,
                          cooldown_windows=0, min_counts=FLOORS,
                          queue_high=1.0, machine_budget=static_machines)
    t0 = time.perf_counter()
    tr = Controller(pol).run(base, srv, crowd, seeds=SEEDS,
                             n_steps=N_STEPS, resizable=[rz])
    us = (time.perf_counter() - t0) * 1e6
    hit = int(np.argmax(crowd == crowd.max()))
    floor = int(tr.machines[:hit].min())
    recovered = int(tr.machines[hit:].max())
    assert recovered > floor, (floor, recovered)
    assert tr.peak_machines <= static_machines
    rows.append(("autoscale/flash_crowd", us,
                 f"controller had drained to {floor} machines at base "
                 f"load; the crowd (window {hit}) pulls it back to "
                 f"{recovered} (budget {static_machines}), "
                 f"{len(tr.actions)} resizes, machine_time "
                 f"{tr.machine_time:.2f} vs static {static_machines}"))

    # -- (config x policy) grid: CompiledSweep.autoscale -------------------
    spec = SweepSpec(n_proxy_leaders=(4, 8), n_replicas=(4,))
    grid = compile_sweep(spec)
    short = diurnal_load(8, low=0.2, sharpness=2.0)
    t0 = time.perf_counter()
    traces = grid.autoscale(alpha, [policies[0], None], short,
                            workload=w, seeds=SEEDS, n_steps=N_STEPS)
    us = (time.perf_counter() - t0) * 1e6
    best = min((t for t in traces if t.policy is not None),
               key=lambda t: t.machine_time)
    rows.append((f"autoscale/grid_{len(grid)}x2", us,
                 f"{len(grid)} configs x 2 policies = {len(traces)} lanes, "
                 f"probes shared, one batched replay; best lane "
                 f"{best.label}: machine_time {best.machine_time:.2f} "
                 f"(static {int(best.servers0.sum())})"))

    # -- execution plane: replay the plan on a real cluster ----------------
    exe_cfg = {"f": 1, "n_proxy_leaders": 4, "grid_rows": 2,
               "grid_cols": 2, "n_replicas": 3}
    ctl = Controller(AutoscalePolicy(target_low=0.45, target_high=0.75,
                                     cooldown_windows=0))
    plan = ctl.run_config(exe_cfg, diurnal_load(6, low=0.3), alpha=alpha,
                          workload=w, seeds=SEEDS, n_steps=3000)
    t0 = time.perf_counter()
    exe = run_autoscaled("compartmentalized", plan, config=exe_cfg,
                         workload=w, n_commands_per_window=30, seed=3)
    us = (time.perf_counter() - t0) * 1e6
    assert exe.passed, exe.describe()
    dips = ", ".join(f"w{r['window']} {r['measured']:.2f}/"
                     f"{r['predicted']:.2f}" for r in exe.dip_rows
                     if r["predicted"] is not None)
    rows.append(("autoscale/execution_replay", us,
                 f"{len(exe.epochs)} epochs over {len(exe.load)} windows "
                 f"on the real cluster: linearizable across every resize, "
                 f"state carried (continuity {exe.continuity_ok}); "
                 f"measured/predicted resize dips {dips} "
                 f"(tolerance {exe.tolerance:.2f})"))

    # -- the capacity anchor, measured on the execution plane --------------
    t0 = time.perf_counter()
    cap = measured_capacity("compartmentalized", workload=w,
                            n_commands=36 if SMOKE else 72, seeds=2)
    us = (time.perf_counter() - t0) * 1e6
    rows.append(("autoscale/capacity_anchor", us,
                 f"saturated capacity {cap:.0f} cmds/s off the batched "
                 f"executor - the execution-plane twin of the transient "
                 f"probe that anchors u = lambda * d"))

    # -- the machine-readable perf anchor ----------------------------------
    root = Path(__file__).resolve().parents[1]
    out = (root / "results" / "BENCH_autoscale.json" if SMOKE
           else root / "BENCH_autoscale.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    payload = {
        "schedule": "diurnal",
        "windows": int(W_DIURNAL),
        "smoke": SMOKE,
        "static_machines": static_machines,
        "machine_time_autoscaled": round(tune.winner.machine_time, 4),
        "machine_time_static": round(tune.static.machine_time, 4),
        "machine_hours_saved_fraction": round(saved, 4),
        "peak_p99_autoscaled_s": float(tune.winner.peak_p99),
        "peak_p99_static_s": float(tune.static.peak_p99),
        "winner_policy": tune.winner.policy.describe(),
        "trough_floor_machines": int(tune.winner.trace.machines.min()),
        "resizes": len(tune.winner.trace.actions),
        "execution_replay": {
            "variant": "compartmentalized",
            "passed": bool(exe.passed),
            "epochs": len(exe.epochs),
            "windows": len(exe.load),
            "dip_tolerance": exe.tolerance,
        },
    }
    out.write_text(json.dumps(payload, indent=2) + "\n")
    rows.append(("autoscale/bench_json", 0.0,
                 f"wrote {out.relative_to(root)}: "
                 f"{saved:.0%} machine-hours saved at p99 "
                 f"{tune.winner.peak_p99:.2e}s vs static "
                 f"{tune.static.peak_p99:.2e}s"))
    return rows
