"""Benchmark harness - one module per paper table/figure.

  fig28  latency-throughput (MultiPaxos / Compartmentalized / unreplicated)
  fig29  compartmentalization ablation staircase (+ batched variant)
  fig30/31  read scalability + closed-form law
  fig32  weakly consistent reads
  fig33  skew tolerance vs CRAQ (incl. scripted skew ramp)
  failover  transient dynamics: leader crash, mid-run scale-up, batch fill
  msgcount  measured-vs-analytical parity per executable variant (registry loop)
  measured  batched execution plane: a config x seed grid of closed-loop
            clients measured in ONE jitted device call
  sweep  whole-surface config sweep + budget autotune (one jitted call)
  variants  protocol-variant plane: Mencius + S-Paxos vs baselines (Figs. 24-28)
  multileader  BPaxos + ISS-bucket contenders: budget staircase, dep-service
            floor, mixed tensor, measured parity + rotation feedback
  shards  the shard axis: scaling, skew, budget splits, live resharding
  geo  geo-replication plane: WAN latency surfaces, placement autotune,
            per-region measured parity, region-partition transient
  autoscale  elastic control loop: diurnal policy search (autoscaled vs
            static-peak machine-hours at equal p99), flash crowd,
            execution-plane replay with dip parity
  roofline  dry-run roofline readout (40 cells x 2 meshes)

Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.runtime.compile_cache import enable_compile_cache

from . import (
    ablation,
    autoscale,
    failover,
    geo,
    latency_throughput,
    measured_surface,
    multileader,
    protocol_messages,
    read_scalability,
    roofline_report,
    shards,
    skew,
    sweep,
    variants,
    weak_reads,
)

MODULES = [
    ("fig28", latency_throughput),
    ("fig29", ablation),
    ("fig30_31", read_scalability),
    ("fig32", weak_reads),
    ("fig33", skew),
    ("failover", failover),
    ("msgcount", protocol_messages),
    ("measured", measured_surface),
    ("sweep", sweep),
    ("variants", variants),
    ("multileader", multileader),
    ("shards", shards),
    ("geo", geo),
    ("autoscale", autoscale),
    ("roofline", roofline_report),
]

EPILOG = """\
benchmarks (label: paper target, typical runtime on one CPU core):
  fig28     Fig. 28  latency-throughput curves, 5 deployments x 512 clients
            via one batched jitted MVA call + stochastic transient
            cross-check (5 deployments x 8 seeds, one scan)     (~10 s)
  fig29     Fig. 29  ablation staircase, batched eval + the autotuner's
            greedy rediscovery of the paper's hand-tuned order  (<1 s)
  fig30_31  Figs. 30-31  read scalability over replicas + closed-form law
            (one compiled replica axis, re-weighted per mix)    (<1 s)
  fig32     Fig. 32  weakly consistent reads skip acceptors; all 6
            deployments per mix on the batched transient engine (~8 s)
  fig33     Fig. 33  skew: flat compartmentalized vs CRAQ dirty-read
            model, a scripted skew ramp p:0->1 mid-run on the batched
            transient engine, + in-process CRAQ cluster         (~15 s)
  failover  transient dynamics on the batched stochastic engine:
            leader crash -> throughput dips to zero and recovers to
            the plateau (p99 carries the stall), mid-run proxy
            scale-up migrating the bottleneck, batch fill ramp
            B:1->100, bursty-arrival p99 via Workload(arrival=
            "bursty"), and p99-under-crash autotuning            (~30 s)
  msgcount  sections 3/6/7  measured-vs-analytical msgs/cmd parity for
            every executable variant (one registry loop: executes the
            real clusters, checks linearizability, validates every
            demand table; BENCH_SMOKE=1 shrinks = make parity-smoke) (~10 s)
  measured  batched execution plane: a config x seed grid of closed-loop
            client populations runs in ONE jitted device call
            (CompiledSweep.execute) with probe-calibrated per-station
            costs; measured msgs/cmd vs the MVA table per grid row,
            validate_batched parity for every executable variant, and
            batched latency p50/p99 off the Pallas histogram kernel;
            BENCH_SMOKE=1 shrinks = make measured-smoke            (~15 s)
  sweep     section 9  "how should a system be compartmentalized":
            300-config surface in one jitted call + budget-19
            autotune for three workload mixes                   (~5 s)
  variants  sections 6-7, Figs. 24-28  "a technique, not a protocol":
            compartmentalized Mencius / S-Paxos beat their vanilla
            baselines; a mixed-variant grid (6 protocols) lowered to
            one demand tensor and solved by one batched MVA call;
            Mencius skip-storm + S-Paxos payload-ramp transients;
            cross-variant budget-19 autotune (which protocol wins?)
            BENCH_SMOKE=1 shrinks the transients                (~10 s)
  multileader  multi-leader family: which protocol wins at budget B?
            the staircase with BPaxos + ISS-bucket contenders, the
            BPaxos dep-service floor vs proposer 1/p split, a mixed
            classic+multi-leader demand tensor in one MVA call, and
            measured parity incl. the ISS rotation/forwarding feedback
            loop; BENCH_SMOKE=1 shrinks = make multileader-smoke (~10 s)
  shards    the shard axis through every plane: uniform shard-count
            scaling (min-law exactly linear, S=1..8 in one flattened
            MVA call), skewed hot shard + autotune_sharded's
            asymmetric budget split, the live-resharding transient
            (hot-shard split under load: dip then recover above the
            pre-split level), and a measured 4-shard deployment with
            per-shard parity + per-key-partition linearizability;
            BENCH_SMOKE=1 shrinks = make shard-smoke            (~10 s)
  geo       geo plane: the (config x region) WAN latency surface in one
            CompiledSweep.geo_latency call, placement autotuning (hub
            beats every pinned placement for spread clients), per-region
            measured parity under the WAN matrix, batched region lanes,
            region-partition transient, calibration stability;
            BENCH_SMOKE=1 shrinks = make geo-smoke              (~15 s)
  autoscale elastic control loop: diurnal policy search in one batched
            replay (autoscaled beats static-peak machine-hours >= 25%
            at equal-or-better worst-window p99, BENCH_autoscale.json),
            flash-crowd re-provisioning under a machine budget, the
            (config x policy) CompiledSweep.autoscale grid, and the
            run_autoscaled execution replay - linearizable across every
            resize, dips parity-checked against the transient;
            BENCH_SMOKE=1 shrinks = make autoscale-smoke        (~60 s)
  roofline  dry-run roofline readout, needs results/dryrun/     (<1 s)

run a subset:    python -m benchmarks.run --only fig28,sweep
full docs:       benchmarks/README.md
"""


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(
        prog="python -m benchmarks.run",
        description=__doc__.split("\n")[0],
        epilog=EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "--only", default=None, metavar="LABELS",
        help="comma-separated benchmark labels to run (default: all)")
    args = parser.parse_args(argv)

    selected = MODULES
    if args.only:
        wanted = {w.strip() for w in args.only.split(",")}
        unknown = wanted - {label for label, _ in MODULES}
        if unknown:
            parser.error(f"unknown benchmark label(s): {sorted(unknown)}; "
                         f"choose from {[l for l, _ in MODULES]}")
        selected = [(l, m) for l, m in MODULES if l in wanted]

    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for label, mod in selected:
        t0 = time.perf_counter()
        try:
            rows = mod.run()
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{label}/ERROR,0.0,\"{e!r}\"")
            traceback.print_exc(file=sys.stderr)
            continue
        wall_us = (time.perf_counter() - t0) * 1e6
        for name, us, derived in rows:
            d = str(derived).replace('"', "'")
            print(f'{name},{us:.1f},"{d}"')
        print(f"{label}/total,{wall_us:.1f},\"module wall time\"")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
