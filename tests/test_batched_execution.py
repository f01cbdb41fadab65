"""Cross-plane agreement: the batched executor vs the measured plane.

The batched execution plane promises that "measured" surfaces (one jitted
device call over a config x seed grid of closed-loop clients) agree with
the scalar measured plane (:func:`run_variant`'s real message-passing
cluster) - probe-calibrated, not copied: the probes run at sizes/seeds
disjoint from every reference run below.  These tests pin that promise
for ALL registered executables - the list comes from the registry via
the ``executable_variant`` fixture (tests/conftest.py), so a newly
registered variant inherits the cross-plane suite with zero edits here -
plus the grid acceptance shape, the quorum-grid acceptor parity, the
leader-crash replay whose recovery dip must match the transient plane's
prediction, and the device loop that stops once every lane has drained.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.batched_execution as bx
from repro.core import tracing
from repro.core.api import (
    MIXED_50_50,
    WRITE_ONLY,
    GeoSpec,
    ShardingSpec,
    Workload,
    register_variant,
    temporary_variants,
    variant_spec,
)
from repro.core.analytical import calibrate_alpha, vanilla_mencius_model
from repro.core.batched_execution import (
    BatchedExecutionResult,
    execute_configs,
    run_variant_batched,
    validate_batched,
)
from repro.core.execution import default_config, run_variant
from repro.core.linearizability import check_linearizable
from repro.core.protocols import CompartmentalizedMultiPaxos, DeploymentConfig
from repro.core.simulator import demand_vector
from repro.core.sweep import SweepSpec, compile_sweep
from repro.core.transient import failover_schedule, simulate_transient

MIXES = [WRITE_ONLY, MIXED_50_50]
N_CMDS = 48

_CACHE = {}


def _batched(name, w, **kw):
    key = (name, w.f_write, tuple(sorted(kw.items())))
    if key not in _CACHE:
        _CACHE[key] = run_variant_batched(name, workload=w,
                                          n_commands=N_CMDS, seeds=2, **kw)
    return _CACHE[key]


# ---------------------------------------------------------------------------
# Satellite: cross-plane agreement for every executable at two mixes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mix", MIXES, ids=lambda w: f"fw{w.f_write:g}")
def test_cross_plane_agreement(executable_variant, mix):
    """Batched per-station msgs/cmd matches run_variant within the
    variant's registered tolerances - exactly on its exact_stations."""
    name = executable_variant
    exe = variant_spec(name).executable
    res = _batched(name, mix)
    ref = run_variant(name, workload=mix, n_commands=N_CMDS, seed=0)
    row = res.station_row(0)
    ref_row = ref.station_msgs
    assert set(row) == set(ref_row), (row, ref_row)
    for st in ref_row:
        m, r = row[st], ref_row[st]
        if st in exe.exact_stations:
            assert abs(m - r) <= 1e-9, (name, st, m, r)
        else:
            tol = exe.tolerance_for(st)
            assert abs(m - r) <= tol * max(r, 1e-12), (name, st, m, r, tol)


def test_quantile_and_drain_pins(executable_variant):
    """p50 <= p99 on every lane; every lane drains its full op budget at
    the exact generator write count; histogram mass == completions."""
    name = executable_variant
    res = _batched(name, MIXED_50_50)
    exe = variant_spec(name).executable
    assert np.all(res.latency_p50 <= res.latency_p99 + 1e-12)
    assert np.all(res.latency_p50 > 0) and np.all(res.latency_mean > 0)
    assert np.all(res.completed == N_CMDS)
    f_eff = 1.0 if exe.reads_as_writes else MIXED_50_50.f_write
    assert res.n_writes[0] == round(N_CMDS * f_eff)
    assert np.all(res.hist.sum(axis=-1) == N_CMDS)
    assert np.all(res.throughput > 0)


def test_latency_monotone_in_load():
    """Closed-loop queueing: more concurrent clients -> strictly more
    queueing delay per command (same budget, same service demands)."""
    lo = _batched("compartmentalized", WRITE_ONLY, n_clients=2)
    hi = _batched("compartmentalized", WRITE_ONLY, n_clients=16)
    assert np.all(hi.latency_mean > lo.latency_mean)
    assert np.all(hi.latency_p99 >= lo.latency_p99)


def test_station_surface_is_seed_independent():
    """The measured msgs/cmd surface depends on the realized mix, not the
    seed: every lane drains round(n * f_write) writes by construction."""
    a = run_variant_batched("compartmentalized", workload=MIXED_50_50,
                            n_commands=N_CMDS, seeds=[0, 1])
    b = run_variant_batched("compartmentalized", workload=MIXED_50_50,
                            n_commands=N_CMDS, seeds=[7, 11])
    np.testing.assert_allclose(a.station_msgs, b.station_msgs, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# Acceptance: one device call over a >= 8-config x >= 4-seed grid
# ---------------------------------------------------------------------------


def test_grid_acceptance_one_call():
    sw = compile_sweep(SweepSpec(
        variants=("compartmentalized", "multipaxos"),
        n_proxy_leaders=(2, 3, 4, 5), n_replicas=(2, 3)))
    assert len(sw.configs) >= 8
    res = sw.execute(workload=MIXED_50_50, n_commands=40, seeds=4)
    assert isinstance(res, BatchedExecutionResult)
    assert len(res) >= 8 and len(res.seeds) >= 4
    assert np.all(res.completed == 40)
    assert np.all(res.latency_p50 <= res.latency_p99 + 1e-12)
    # measured surface of every row agrees with its analytical demand
    # table within the variant's registered tolerances
    for m in range(len(res)):
        name = res.variant(m)
        exe = variant_spec(name).executable
        w = MIXED_50_50
        realized = Workload(
            f_write=1.0 if exe.reads_as_writes else w.f_write)
        predicted = variant_spec(name).model(res.configs[m], w).demands(
            realized)
        for st, mm in res.station_row(m).items():
            p = predicted.get(st, 0.0)
            assert abs(mm - p) <= exe.tolerance_for(st) * max(p, 1e-12), (
                name, st, mm, p)


def test_execute_requires_configs_and_plane():
    with temporary_variants():
        register_variant(name="table_only_bx", factory=vanilla_mencius_model,
                         stations=("server",))
        with pytest.raises(ValueError, match="no execution plane"):
            run_variant_batched("table_only_bx")
        with pytest.raises(ValueError, match="no execution plane"):
            execute_configs([{"variant": "table_only_bx"}])


# ---------------------------------------------------------------------------
# Satellite: measured-vs-analytical parity on the batched plane
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["compartmentalized", "craq",
                                  "vanilla_spaxos", "multipaxos",
                                  "bpaxos", "iss"])
def test_validate_batched_passes(name):
    rep = validate_batched(name, workload=MIXED_50_50, n_commands=N_CMDS,
                           seeds=2)
    assert rep.passed, str(rep)
    assert rep.max_rel_err() < 1.0
    assert "batched" in str(rep)


# ---------------------------------------------------------------------------
# Satellite: 2-row write vs 2-column read quorum grids through the
# executable plane - acceptor msgs/cmd pinned against the analytical table
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mix", MIXES, ids=lambda w: f"fw{w.f_write:g}")
def test_quorum_grid_sweep_acceptor_parity(mix):
    grids = [(2, 2), (2, 3), (3, 2)]
    configs = [{"variant": "compartmentalized",
                "grid_rows": r, "grid_cols": c} for r, c in grids]
    res = execute_configs(configs, workload=mix, n_commands=40, seeds=2)
    spec = variant_spec("compartmentalized")
    acc = []
    for m, cfg in enumerate(configs):
        measured = res.station_row(m)["acceptor"]
        predicted = spec.model(cfg, mix).demands(mix)["acceptor"]
        if mix.f_write >= 1.0:
            # write path is deterministic: exact table parity
            assert abs(measured - predicted) <= 1e-9, (cfg, measured,
                                                       predicted)
        else:
            tol = spec.executable.tolerance_for("acceptor")
            assert abs(measured - predicted) <= tol * predicted, (
                cfg, measured, predicted)
        acc.append(measured)
    # the table's asymmetry: with 2-member write quorums (columns of a
    # 2-row grid), widening the grid spreads the same write traffic over
    # more acceptors - (2, 3) is strictly cheaper per acceptor than (2, 2)
    # and than 3-member write columns ((3, 2)) under writes; at 50/50 the
    # transposed grids tie exactly (write and read quorums swap roles)
    assert acc[1] < acc[0], acc
    if mix.f_write >= 1.0:
        assert acc[1] < acc[2], acc
    else:
        assert abs(acc[1] - acc[2]) <= 1e-9, acc


# ---------------------------------------------------------------------------
# The device loop stops at the first chunk boundary after the drain
# ---------------------------------------------------------------------------

LOOP_GRID = [{"variant": "compartmentalized", "n_proxy_leaders": 2},
             {"variant": "compartmentalized", "n_proxy_leaders": 3,
              "n_replicas": 3},
             {"variant": "multipaxos"}]
LOOP_CASES = {
    "deterministic": dict(configs=LOOP_GRID, workload=MIXED_50_50),
    "exponential": dict(configs=LOOP_GRID, workload=MIXED_50_50,
                        exponential_service=True),
    # the third shard's weight is 0: a lane with no ops, drained at step 0
    "sharded_zero_budget": dict(
        configs=LOOP_GRID[:2], workload=Workload.read_mix(0.6),
        sharding=ShardingSpec(3, weights=(0.6, 0.4, 0.0))),
}


@pytest.fixture
def fresh_programs():
    """Programs traced with a module function replaced are not reused."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _full_bound_lane(d_w, d_r, entry, nxt, cls_stream, budget, dt, key,
                     n_steps, n_clients, exponential):
    """The same lane's step function under one ``lax.scan`` over every
    step of the bound."""
    state0, step, draws = bx._exec_lane(d_w, d_r, entry, nxt, cls_stream,
                                        budget, dt, key, n_steps, n_clients,
                                        exponential)
    xs = (jnp.arange(n_steps, dtype=jnp.int32), draws)
    state, (fin, lat) = jax.lax.scan(step, state0, xs)
    return (fin, lat) + tuple(state[6:9])


def _stalled_lane(real):
    """Each step drains a billionth of the work: no lane completes an op
    within the bound."""
    def stalled(d_w, d_r, entry, nxt, cls_stream, budget, dt, *rest):
        return real(d_w, d_r, entry, nxt, cls_stream, budget, dt * 1e-9,
                    *rest)
    return stalled


def _execute_recorded(monkeypatch, configs, outs=None, **kwargs):
    """execute_configs, and the device outputs of its one scan call."""
    outs = [] if outs is None else outs
    real = bx._execute_batch

    def recorded(*args, **kw):
        out = real(*args, **kw)
        outs.append(jax.device_get(out))
        return out
    monkeypatch.setattr(bx, "_execute_batch", recorded)
    try:
        # 96 ops over 4 clients: lanes drain in the second or the third
        # chunk, each run several chunks short of its bound
        res = execute_configs(configs, n_commands=96, seeds=3, n_clients=4,
                              probe_n=12, **kwargs)
    finally:
        monkeypatch.setattr(bx, "_execute_batch", real)
    return res, outs[0], tracing.recent("repro.execute", 1)[0].counts


def _steps_run(counts, res):
    lanes = len(res) * len(res.seeds)
    return counts["repro.execute.scan_lane_steps"] // lanes


def _steps_on_device(lat):
    """Steps the device ran, from its samples: on such a step every client
    has a positive sample time (the step's end less its op's start),
    whether or not it completed; the steps it never ran stay zero."""
    return np.flatnonzero(np.any(lat != 0, axis=(0, 1, 3)))


@pytest.mark.parametrize("case", sorted(LOOP_CASES))
def test_drain_loop_matches_full_bound_scan(monkeypatch, fresh_programs,
                                            case):
    """The loop that stops at the drain computes what one scan over the
    whole bound computes: the same samples on the steps it ran, no
    completion after them, and bit-identical counts, makespans,
    histograms and latency means."""
    kwargs = dict(LOOP_CASES[case])
    configs = kwargs.pop("configs")
    res, out, counts = _execute_recorded(monkeypatch, configs, **kwargs)
    monkeypatch.setattr(bx, "_one_exec_lane", _full_bound_lane)
    jax.clear_caches()
    ref, ref_out, _ = _execute_recorded(monkeypatch, configs, **kwargs)
    ran = _steps_run(counts, res)
    assert 0 < ran < res.n_steps == ref.n_steps
    assert np.array_equal(_steps_on_device(out[1]), np.arange(ran))
    assert np.array_equal(_steps_on_device(ref_out[1]),
                          np.arange(ref.n_steps))
    if case == "sharded_zero_budget":
        assert np.any(res.lane_commands == 0)
    fin, lat = out[:2]
    ref_fin, ref_lat = ref_out[:2]
    assert np.array_equal(fin[:, :, :ran], ref_fin[:, :, :ran])
    assert not fin[:, :, ran:].any() and not ref_fin[:, :, ran:].any()
    assert np.array_equal(lat[fin], ref_lat[ref_fin])
    for got, want in zip(out[2:], ref_out[2:]):       # done_w, done_r, t_last
        assert np.array_equal(got, want)
    for field in ("completed", "n_writes", "throughput", "hist",
                  "latency_mean", "latency_p50", "latency_p99", "bin_edges"):
        assert np.array_equal(getattr(res, field), getattr(ref, field)), field


@pytest.mark.parametrize("case", ["drains", "stalls"])
def test_drain_loop_stops_at_the_chunk_after_the_last_lane(
        monkeypatch, fresh_programs, case):
    """The device runs whole chunks up to the one that holds the last
    lane's last completion; a lane that cannot drain keeps the loop
    running to the bound, and the guard refuses the result."""
    kwargs = dict(LOOP_CASES["sharded_zero_budget"])
    configs = kwargs.pop("configs")
    if case == "stalls":
        monkeypatch.setattr(bx, "_exec_lane", _stalled_lane(bx._exec_lane))
        outs = []
        with pytest.raises(RuntimeError, match="drained"):
            _execute_recorded(monkeypatch, configs, outs, **kwargs)
        fin, lat = outs[0][:2]
        assert not fin.any()
        assert np.array_equal(_steps_on_device(lat),
                              np.arange(lat.shape[2]))
        return
    res, out, counts = _execute_recorded(monkeypatch, configs, **kwargs)
    lane_steps = np.rint(out[4] / res.dt[:, None])        # t_last / dt
    want = bx.SCAN_CHUNK * int(np.ceil(lane_steps.max() / bx.SCAN_CHUNK))
    assert want < res.n_steps
    assert np.array_equal(_steps_on_device(out[1]), np.arange(want))
    assert _steps_run(counts, res) == want
    assert counts["repro.execute.lane_steps"] == int(lane_steps.sum())


def _outer_loops(jaxpr):
    """The ``while`` equations of a jaxpr and of the calls in it, not
    those nested inside another loop."""
    loops = []
    for e in jaxpr.eqns:
        if e.primitive.name == "while":
            loops.append(e)
        elif "jaxpr" in e.params:                   # a jitted call
            loops += _outer_loops(e.params["jaxpr"].jaxpr)
    return loops


def test_drain_loop_is_one_loop_over_the_batch():
    """The loop's predicate is one value for the whole batch.  A predicate
    per lane would make the vmapped loop select, chunk by chunk, between
    the old and the new sample buffers of each lane, holding both."""
    m, s, n, k, n_steps = 3, 2, 4, 15, 2 * bx.SCAN_CHUNK
    sds = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    args = (sds((m, k), f32), sds((m, k), f32), sds((m,), i32),
            sds((m, k), i32), sds((m, s, n, 6), i32), sds((m, n), i32),
            sds((m,), f32), sds((s,), i32))
    jaxpr = jax.make_jaxpr(lambda *a: bx._execute_batch(
        *a, n_clients=n, n_steps=n_steps, exponential=False))(*args)
    loops = _outer_loops(jaxpr.jaxpr)
    assert len(loops) == 1
    assert (m, s, n_steps, n) in {v.aval.shape for v in loops[0].outvars}
    (pred,) = loops[0].params["cond_jaxpr"].jaxpr.outvars
    assert pred.aval.shape == ()


# ---------------------------------------------------------------------------
# The completed samples are compacted on the device before the pull
# ---------------------------------------------------------------------------

WIDTH = 6


def _completion_steps(n_chunks, rng):
    """Per (lane, client) the steps it completes on: lane (0, 0) holds the
    edge cases, the other lanes draw 0 to WIDTH steps at random."""
    c = bx.SCAN_CHUNK
    n_steps = n_chunks * c
    edge = [[],                                     # no completion
            [5],                                    # one
            [0, c - 1, c, 2 * c - 1, 2 * c, n_steps - 1],   # WIDTH, at edges
            [c - 1, c],                             # adjacent chunks
            list(range(10, 10 + WIDTH)),            # WIDTH in one chunk
            sorted(rng.choice(n_steps, WIDTH - 2, replace=False))]
    steps = np.empty((2, 2, len(edge)), dtype=object)
    for idx in np.ndindex(steps.shape):
        k = int(rng.integers(0, WIDTH + 1))
        steps[idx] = (edge[idx[2]] if idx[:2] == (0, 0)
                      else sorted(rng.choice(n_steps, k, replace=False)))
    return steps, n_steps


@pytest.mark.parametrize("n_chunks", [3, 5])
@pytest.mark.parametrize("latencies", ["distinct", "equal"])
def test_completed_latencies_are_each_clients_samples(n_chunks, latencies):
    """Slot j of a client holds the latency of its j-th completion, in step
    order, and 0 past its last; their float64 sum is the masked sum over
    every step; the walk ends at the lanes' completion counts."""
    rng = np.random.default_rng(n_chunks)
    steps, n_steps = _completion_steps(n_chunks, rng)
    m, s, n = steps.shape
    fin = np.zeros((m, s, n_steps, n), dtype=bool)
    for (mi, si, ci), at in np.ndenumerate(steps):
        fin[mi, si, at, ci] = True
    lat = (rng.uniform(0.5, 9.0, fin.shape) if latencies == "distinct"
           else np.full(fin.shape, 0.25)).astype(np.float32)
    done = fin.sum(axis=(2, 3), dtype=np.int32)
    samples = np.asarray(bx._completed_latencies(
        jnp.asarray(fin), jnp.asarray(lat), jnp.asarray(done), width=WIDTH))
    assert samples.shape == (m, s, n, WIDTH)
    assert samples.dtype == lat.dtype
    for (mi, si, ci), at in np.ndenumerate(steps):
        k = len(at)
        assert np.array_equal(samples[mi, si, ci, :k], lat[mi, si, at, ci])
        assert not samples[mi, si, ci, k:].any()
    assert np.array_equal(
        samples.astype(np.float64).sum(axis=(2, 3)),
        np.where(fin, lat.astype(np.float64), 0.0).sum(axis=(2, 3)))
    # the walk stops once every lane has counted its completions: none
    # expected, no chunk read
    assert not np.asarray(bx._completed_latencies(
        jnp.asarray(fin), jnp.asarray(lat), jnp.zeros_like(done),
        width=WIDTH)).any()


GEO_3 = GeoSpec(regions=("us", "eu", "ap"),
                rtt=((0, 8, 16), (8, 0, 12), (16, 12, 0)))
REDUCE_CASES = {
    "read_mix": dict(configs=LOOP_GRID, workload=Workload.read_mix(0.6)),
    "sharded_uneven": LOOP_CASES["sharded_zero_budget"],
    "geo": dict(configs=LOOP_GRID[:2], workload=MIXED_50_50, geo=GEO_3),
}


@pytest.mark.parametrize("case", sorted(REDUCE_CASES))
def test_latency_mean_equals_the_reduce_over_every_step(monkeypatch, case):
    """The mean from the compacted samples is, bit for bit, the float64
    masked sum over every step of the scan's outputs, per completion,
    plus the lane's WAN offset."""
    kwargs = dict(REDUCE_CASES[case])
    configs = kwargs.pop("configs")
    res, out, _ = _execute_recorded(monkeypatch, configs, **kwargs)
    fin, lat, done_w, done_r = out[:4]
    lat_sum = np.where(fin, lat.astype(np.float64), 0.0).sum(axis=(2, 3))
    done = done_w.astype(np.int64) + done_r
    wan = np.zeros(len(res)) if res.wan_offset is None else res.wan_offset
    if case == "geo":
        assert np.all(wan > 0.0)
    want = lat_sum / np.maximum(done, 1) + wan[:, None]
    assert res.latency_mean.dtype == want.dtype
    assert np.array_equal(res.latency_mean, want)


def test_pull_does_not_scale_with_the_step_bound():
    """An answer pulls done_w, done_r, the histogram, each client's
    completed samples and the makespans: the same bytes whatever the
    step bound."""
    pulled, bounds = [], []
    for oversample in (4.0, 8.0):
        res = execute_configs(LOOP_GRID[:2], workload=MIXED_50_50,
                              n_commands=24, seeds=2, n_clients=4,
                              probe_n=12, oversample=oversample)
        m, s = res.completed.shape
        width = -(-res.n_commands // res.n_clients)
        assert tracing.recent("repro.execute", 1)[0].counts[
            tracing.PULL_BYTES] == (m * s * 4 * 2 + res.hist.nbytes
                                    + m * s * res.n_clients * width * 4
                                    + m * s * 4)
        pulled.append(tracing.recent("repro.execute", 1)[0].counts[
            tracing.PULL_BYTES])
        bounds.append(res.n_steps)
    assert bounds[0] < bounds[1] and pulled[0] == pulled[1]


# ---------------------------------------------------------------------------
# Satellite: transient leader-crash schedule replayed on the correctness
# plane - linearizable across failover, dip shape matching the prediction
# ---------------------------------------------------------------------------


def _completion_rate(history, t0, t1):
    n = sum(1 for o in history.ops
            if o.response_time is not None and t0 <= o.response_time < t1)
    return n / (t1 - t0)


def test_leader_crash_replay_matches_transient_dip():
    """Replay the transient plane's failover schedule (crash the leader
    mid-run, heartbeat-driven promotion, client rediscovery) on the real
    cluster: the history must stay linearizable across the failover, and
    the completion-rate trace must show the same dip-and-recover shape
    the transient engine predicts for the same schedule."""
    # --- prediction: scripted leader crash through the scan engine ------
    alpha = calibrate_alpha()
    model = variant_spec("compartmentalized").model(
        default_config("compartmentalized"), WRITE_ONLY)
    base = demand_vector(model, f_write=1.0) / alpha
    sched, bounds = failover_schedule(base, "leader", start=0.35, stop=0.6,
                                      n_steps=1200)
    tr = simulate_transient(sched, bounds, n_clients=16, seeds=4,
                            n_steps=1200)
    centers, x = tr.throughput_trace(n_windows=24)
    frac = centers[0] / centers[0, -1] / (24 / 23.5)  # window fractions
    pre_p = x[0, :, (frac > 0.05) & (frac < 0.3)].mean()
    dip_p = x[0, :, (frac > 0.4) & (frac < 0.55)].mean()
    post_p = x[0, :, (frac > 0.7)].mean()
    assert dip_p < 0.25 * pre_p, (dip_p, pre_p)
    assert post_p > 0.4 * pre_p, (post_p, pre_p)

    # --- replay: the same schedule against the real cluster -------------
    cfg = DeploymentConfig(f=1, n_proxy_leaders=3, grid=(2, 2),
                           n_replicas=2, state_machine="register", seed=0,
                           client_retries=True, auto_failover=True)
    dep = CompartmentalizedMultiPaxos(cfg, n_clients=2)
    for i, c in enumerate(dep.clients):
        c.run_ops([("w", 1000 * i + j) for j in range(300)])
    dep.net.run(until=400)                      # steady phase
    dep.net.crash("leader/0")
    dep.net.run(until=1_600)                    # outage until promotion
    assert dep.leaders[1].active, "heartbeats must promote a new leader"
    for c in dep.clients:                       # client-side rediscovery
        c.leader = "leader/1"
    dep.net.run(until=3_000)                    # recovery phase

    pre = _completion_rate(dep.history, 0, 400)
    dip = _completion_rate(dep.history, 500, 1_500)
    post = _completion_rate(dep.history, 1_700, 3_000)
    assert pre > 0, "no completions in the steady phase"
    # same shape booleans the transient plane predicted above
    assert dip < 0.25 * pre, (dip, pre)
    assert post > 0.4 * pre, (post, pre)
    assert check_linearizable(dep.history, "register")
