#!/usr/bin/env python3
"""Bring-up smoke test: the evaluation engines end to end on one TPU chip.

Drives the four device engines through their public entry points, on the
paper's f = 1 deployments under the write-only and 90%-read mixes, and
checks every answer against a reference:

  mva        the 300-config surface of benchmarks/sweep.py, one
             CompiledSweep.mva call per mix; rows vs a float64 NumPy MVA
  transient  the five Fig. 28 deployments x 8 seeds x 512 clients x 16,384
             steps through CompiledSweep.transient; seed-mean throughput
             within 5% of MVA's X(512)
  execute    the Fig. 29a staircase through CompiledSweep.execute: every
             lane drains its budget, msgs/cmd within each variant's parity
             tolerance, the Pallas histogram equal to its jnp reference on
             the same samples, one linearizable host run
  autoscale  the diurnal policy search of benchmarks/autoscale.py at its
             full size: compiles inside the call, machine budget respected

Each phase prints one line: its sizes, compile seconds (JAX's own compile
events, persistent-cache loads included), wall seconds on the host clock
ended by ``block_until_ready``, and its check.  The last line is one JSON
object naming the device.  Without a TPU it exits non-zero before any
phase; a failed check raises.

    python chip_smoke.py
"""
from __future__ import annotations

import collections
import json
import math
import sys
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

# Workloads: the paper's two mixes.
MIXES = ("write_only", "read_90")
# MVA surface: rows checked against the float64 reference.
MVA_CLIENTS = 512
MVA_CHECK_ROWS = (0, 75, 149, 150, 225, 299)
MVA_RTOL = 1e-4
# Transient: Fig. 28 deployments x seeds x clients x steps.
TRANSIENT = dict(seeds=8, n_clients=512, n_steps=16_384)
TRANSIENT_RTOL = 0.05
# Execution grid: vanilla MultiPaxos plus compartmentalized MultiPaxos at
# Fig. 29's proxy-leader and replica counts on the paper's 2x2 grid, x
# seeds x clients x commands per lane.  Per mix, SEEDS is the largest
# count whose execute program stays under 8 GiB on a v5e and STEPS the
# makespan bound execute_configs derives (tests/test_tpu_compile.py
# compiles both).
EXEC_KNOBS = dict(variants=("multipaxos", "compartmentalized"),
                  n_proxy_leaders=(3, 5, 7, 10), grids=((2, 2),),
                  n_replicas=(2, 4))
EXEC = dict(n_clients=64, n_commands=4096)
EXEC_SEEDS = {"write_only": 8, "read_90": 4}
EXEC_STEPS = {"write_only": 87_296, "read_90": 178_688}
EXEC_BYTES_LIMIT = 8 * 2**30
EXEC_PROBE_N = 40
LINEARIZABLE_N = 120
HIST_REF_CHUNK = 4096

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    """Sums JAX's compile-event durations and counts backend compiles
    (persistent-cache loads included) per jitted function name."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.programs: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
        if event == _COMPILE_EVENTS[-1]:
            self.programs[kwargs.get("fun_name", "?")] += 1


class Phase:
    """Times the engine calls of one phase (wall, and compile within it)
    and prints its line; reference checks run outside the timed calls."""

    def __init__(self, meter: CompileMeter, name: str) -> None:
        self.meter, self.name = meter, name
        self.wall = self.compile_s = 0.0
        self.programs: collections.Counter = collections.Counter()

    def run(self, fn, *args, **kwargs):
        compile_s0 = self.meter.seconds
        programs0 = collections.Counter(self.meter.programs)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        self.wall += time.perf_counter() - t0
        self.compile_s += self.meter.seconds - compile_s0
        self.programs += self.meter.programs - programs0
        return out

    def report(self, sizes: str, check: str) -> None:
        print(f"{self.name} | {sizes} | compile {self.compile_s:.6f} s "
              f"({sum(self.programs.values())} programs) | wall "
              f"{self.wall:.6f} s | {check}", flush=True)


def check(ok, *detail) -> None:
    """A result check that also holds under ``python -O``."""
    if not ok:
        raise AssertionError(detail)


def _workload(mix: str):
    from repro.core.api import Workload
    return (Workload(f_write=1.0, name="write_only") if mix == "write_only"
            else Workload.read_mix(0.9))


def mva_reference(d: np.ndarray, n_max: int):
    """Exact single-class MVA in float64: X(n), R(n) for n = 1..n_max."""
    q = np.zeros_like(d)
    xs, rs = np.empty(n_max), np.empty(n_max)
    for n in range(1, n_max + 1):
        r_k = d * (1.0 + q)
        xs[n - 1] = n / r_k.sum()
        rs[n - 1] = r_k.sum()
        q = xs[n - 1] * r_k
    return xs, rs


def phase_mva(meter, alpha, n_clients=MVA_CLIENTS, rows=MVA_CHECK_ROWS):
    from benchmarks.sweep import surface_grid
    ph = Phase(meter, "mva")
    grid = surface_grid()
    worst = 0.0
    for mix in MIXES:
        w = _workload(mix)
        _, x, r = ph.run(grid.mva, alpha, n_clients_max=n_clients,
                         workload=w)
        d = grid.demands(w) / alpha
        for i in rows:
            x_ref, r_ref = mva_reference(d[i], n_clients)
            err = max(np.max(np.abs(x[i] - x_ref) / x_ref),
                      np.max(np.abs(r[i] - r_ref) / r_ref))
            check(err <= MVA_RTOL, mix, i, err)
            worst = max(worst, float(err))
    ph.report(f"{len(grid)} configs x {n_clients} clients x {len(MIXES)} "
              f"mixes", f"X, R of rows {list(rows)} within {MVA_RTOL:g} of "
              f"float64 MVA (max rel err {worst:.3e})")


def phase_transient(meter, alpha, seeds, n_clients, n_steps):
    from benchmarks.latency_throughput import fig28_models
    from repro.core.sweep import compile_models
    ph = Phase(meter, "transient")
    grid = compile_models(fig28_models())
    worst = 0.0
    for mix in MIXES:
        w = _workload(mix)
        res = ph.run(grid.transient, alpha, n_clients=n_clients, workload=w,
                     seeds=seeds, n_steps=n_steps)
        _, x_mva, _ = grid.mva(alpha, n_clients_max=n_clients, workload=w)
        x = res.seed_mean_throughput()
        rel = np.abs(x - x_mva[:, -1]) / x_mva[:, -1]
        check(np.all(rel <= TRANSIENT_RTOL), mix, rel)
        worst = max(worst, float(rel.max()))
    ph.report(f"{len(grid)} deployments x {seeds} seeds x {n_clients} "
              f"clients x {n_steps} steps x {len(MIXES)} mixes",
              f"seed-mean throughput within {TRANSIENT_RTOL:g} of MVA "
              f"X({n_clients}) (max rel err {worst:.3e})")


@partial(jax.jit, static_argnames="chunk")
def _ref_hist_chunked(samples, valid, edges, chunk):
    """``ref.ref_latency_hist`` over column chunks of the samples, summed:
    the jnp reference at a size whose one-hot fits in device memory."""
    from repro.kernels import ref
    lanes, n = samples.shape
    s = samples.reshape(lanes, n // chunk, chunk)
    v = valid.reshape(lanes, n // chunk, chunk)

    def add(acc, k):
        return acc + ref.ref_latency_hist(s[:, k], v[:, k], edges), None

    acc0 = jnp.zeros((lanes, edges.shape[1] - 1), jnp.int32)
    return jax.lax.scan(add, acc0, jnp.arange(n // chunk))[0]


def _execute_bytes(res, n_commands):
    """memory_analysis() of the execute program at this result's shapes."""
    from repro.core.analytical import STATION_ORDER
    from repro.core.batched_execution import _execute_batch
    m, s, n, k = len(res), len(res.seeds), res.n_clients, len(STATION_ORDER)
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    args = (sds((m, k), f32), sds((m, k), f32), sds((m,), i32),
            sds((m, k), i32), sds((m, s, n, max(-(-n_commands // n), 1)), i32),
            sds((m, n), i32), sds((m,), f32), sds((s,), i32))
    return _execute_batch.lower(*args, n_clients=n, n_steps=res.n_steps,
                                exponential=False).compile().memory_analysis()


def phase_execute(meter, alpha, seeds, n_clients, n_commands, steps=None,
                  probe_n=EXEC_PROBE_N):
    """``seeds`` (and ``steps``, when given) map each mix to its value."""
    import repro.core.batched_execution as bx
    from repro.core.execution import run_variant
    from repro.core.sweep import SweepSpec, compile_sweep
    from repro.kernels import ops

    ph = Phase(meter, "execute")
    grid = compile_sweep(SweepSpec(**EXEC_KNOBS))
    paper = dict(grid.configs[-1])      # 10 proxy leaders, 2x2, 4 replicas
    checks = []
    for mix in MIXES:
        w = _workload(mix)
        seen = {}

        def recording_hist(samples, valid, edges):
            # the engine's own kernel call, with its inputs kept so the
            # reference can run on the very same samples
            seen.update(samples=samples, valid=valid, edges=edges)
            return ops.latency_hist(samples, valid, edges)

        bx.latency_hist = recording_hist
        try:
            res = ph.run(grid.execute, workload=w, n_commands=n_commands,
                         seeds=seeds[mix], n_clients=n_clients, alpha=alpha,
                         probe_n=probe_n, max_steps=250_000)
        finally:
            bx.latency_hist = ops.latency_hist
        if steps is not None:
            check(res.n_steps == steps[mix], mix, res.n_steps)
        ma = _execute_bytes(res, n_commands)
        total = ma.output_size_in_bytes + ma.temp_size_in_bytes
        check(total < EXEC_BYTES_LIMIT, mix, total)

        check(np.all(res.completed == n_commands), mix, res.completed)
        parity = [bx.batched_parity(res, i, probe_n=probe_n)
                  for i in range(len(res))]
        bad = [str(p) for p in parity if not p.passed]
        check(not bad, "\n".join(bad))
        n_samples = seen["samples"].shape[1]
        ref_hist = _ref_hist_chunked(
            seen["samples"], seen["valid"], seen["edges"],
            chunk=math.gcd(n_samples, HIST_REF_CHUNK))
        np.testing.assert_array_equal(
            np.asarray(ref_hist).reshape(res.hist.shape), res.hist)
        seen.clear()
        trace = run_variant("compartmentalized", paper, w,
                            n_commands=LINEARIZABLE_N, seed=1)
        check(trace.linearizable, mix, "run_variant not linearizable")
        checks.append(
            f"{mix}: {seeds[mix]} seeds, {res.n_steps} steps, program out "
            f"{ma.output_size_in_bytes} B + temp {ma.temp_size_in_bytes} B, "
            f"max parity err {max(p.max_rel_err() for p in parity):.3e}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    ph.report(f"{len(grid)} configs x {n_clients} clients x {n_commands} "
              f"cmds/lane, device peak {peak} B",
              "budgets drained, msgs/cmd within parity tolerance, histogram "
              "== ref.ref_latency_hist, run_variant linearizable; "
              + "; ".join(checks))


def phase_autoscale(meter, alpha, windows, n_steps, seeds):
    from benchmarks.autoscale import CFG, DIURNAL_POLICIES, demand_row
    from repro.core import autotune_policy, diurnal_load, resizable_stations
    ph = Phase(meter, "autoscale")
    w = _workload("write_only")
    base, srv = demand_row(CFG, w, alpha)
    budget = int(srv.sum())
    rz = resizable_stations("compartmentalized", CFG)
    load = diurnal_load(windows, low=0.15, sharpness=2.0)
    tune = ph.run(autotune_policy, DIURNAL_POLICIES, base, srv, load,
                  p99_slack=1.0, budget=budget, seeds=seeds, n_steps=n_steps,
                  resizable=[rz] * (len(DIURNAL_POLICIES) + 1))
    check(tune.winner.policy is not None, "no policy beat static-peak")
    check(tune.winner.peak_machines <= budget, tune.winner.peak_machines,
          budget)
    saved = 1.0 - tune.winner.machine_time / tune.static.machine_time
    ph.report(f"{len(DIURNAL_POLICIES) + 1} lanes x {windows} windows x "
              f"{seeds} seeds x {n_steps} replay steps",
              f"{ph.programs['jit(_transient_batch)']} _transient_batch "
              f"compiles inside the call; winner peak "
              f"{tune.winner.peak_machines} "
              f"<= budget {budget} machines, machine_time "
              f"{tune.winner.machine_time:.6f} vs static "
              f"{tune.static.machine_time:.6f} ({saved:.6f} saved)")


def main() -> int:
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from benchmarks.autoscale import FULL_SIZE
    from repro.core.analytical import PAPER_MULTIPAXOS_UNBATCHED
    from repro.core.analytical import calibrate_alpha

    print(f"device {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
          f"compile cache {cache}", flush=True)
    meter = CompileMeter()
    alpha = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
    phase_mva(meter, alpha)
    phase_transient(meter, alpha, **TRANSIENT)
    phase_execute(meter, alpha, EXEC_SEEDS, **EXEC, steps=EXEC_STEPS)
    windows, n_steps, seeds = FULL_SIZE
    phase_autoscale(meter, alpha, windows, n_steps, seeds)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
