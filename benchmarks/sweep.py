"""Whole-surface sweep + autotune benchmark (paper section 9: "How should a
system be compartmentalized?").

Compiles a few-hundred-config grid over every compartmentalization knob,
evaluates the full latency-throughput surface in ONE jitted MVA call, and
then asks the autotuner for the best deployment under a machine budget for
three workload mixes - reporting the bottleneck-migration trace that
justifies each answer.
"""
import time

from repro.core.analytical import PAPER_MULTIPAXOS_UNBATCHED, calibrate_alpha
from repro.core.api import Workload
from repro.core.autotune import autotune, candidate_spec
from repro.core.sweep import SweepSpec, compile_models, compile_sweep, model_for

KNOBS = dict(
    n_proxy_leaders=(1, 2, 3, 5, 7, 10),
    grids=((3, 1), (2, 2), (2, 3), (3, 2), (3, 3)),
    n_replicas=(2, 3, 4, 5, 6),
)


def surface_grid():
    """The 300-config surface: every KNOBS point, unbatched and batched."""
    # batch_size > 1 only makes sense with a batcher stage in front (the
    # factory amortizes downstream demand by B), so the batched half of the
    # grid carries batchers/unbatchers instead of crossing B with 0 batchers
    spec_unbatched = SweepSpec(**KNOBS)
    spec_batched = SweepSpec(**KNOBS, batch_sizes=(100,), n_batchers=(2,),
                             n_unbatchers=(3,))
    configs = list(spec_unbatched.configs()) + list(spec_batched.configs())
    return compile_models([model_for(c) for c in configs], configs)


def run():
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    t0 = time.perf_counter()
    compiled = surface_grid()
    compile_us = (time.perf_counter() - t0) * 1e6

    # peak surface: bottleneck law, vectorized over all configs
    t1 = time.perf_counter()
    peaks_w = compiled.peak_throughput(alpha, Workload())
    law_us = (time.perf_counter() - t1) * 1e6

    # full MVA surface: one jitted call over the whole grid
    t2 = time.perf_counter()
    clients, X, _ = compiled.mva(alpha, n_clients_max=256,
                                 workload=Workload())
    mva_us = (time.perf_counter() - t2) * 1e6

    rows = [
        (f"sweep/compile_{len(compiled)}_configs", compile_us,
         "config -> demand-matrix lowering (Python, once)"),
        (f"sweep/bottleneck_law_{len(compiled)}_configs", law_us,
         f"peak surface, max {peaks_w.max():.0f} cmd/s"),
        (f"sweep/mva_one_call_{len(compiled)}x256", mva_us,
         f"X[{X.shape[0]}, {X.shape[1]}] latency-throughput surface, "
         f"single jitted call"),
    ]

    for i, (idx, peak, bn) in enumerate(
            compiled.top_k(alpha, k=3, workload=Workload.read_mix(0.9))):
        cfg = compiled.configs[idx]
        rows.append((f"sweep/top{i+1}_90pct_reads", 0.0,
                     f"{peak:.0f} cmd/s (bn={bn}) p={cfg['n_proxy_leaders']} "
                     f"grid={cfg['grid_rows']}x{cfg['grid_cols']} "
                     f"n={cfg['n_replicas']} B={cfg['batch_size']} "
                     f"batchers={cfg['n_batchers']}"))

    # one compiled candidate space serves all three workload mixes
    candidates = compile_sweep(candidate_spec(budget=19))
    for workload in (Workload(f_write=1.0, name="write_only"),
                     Workload(f_write=0.5, name="50pct_reads"),
                     Workload(f_write=0.1, name="90pct_reads")):
        label = workload.name
        t3 = time.perf_counter()
        res = autotune(budget=19, alpha=alpha, workload=workload,
                       compiled=candidates)
        us = (time.perf_counter() - t3) * 1e6
        migration = " -> ".join(t.bottleneck for t in res.trace)
        rows.append((f"sweep/autotune_budget19_{label}", us,
                     f"best {res.best_peak:.0f} cmd/s @ {res.machines} machines "
                     f"({res.n_candidates} candidates); bottleneck migration: "
                     f"{migration}"))
    return rows
