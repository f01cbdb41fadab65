"""Transient-engine tests: steady-state agreement with the MVA / fluid /
DES engines, seeded determinism across vmapped lanes, scripted-event
dynamics (failover dip + recovery, mid-run scale-up), and the batched
(deployments x seeds)-in-one-jitted-call contract."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Event,
    calibrate_alpha,
    compartmentalized_model,
    compile_sweep,
    des_throughput,
    fluid_throughput,
    multipaxos_model,
    mva_curve,
    scale_schedule,
    schedule_from_demands,
    simulate_transient,
    transient_throughput,
    unreplicated_model,
    SweepSpec,
    Workload,
)
from repro.core.analytical import PAPER_MULTIPAXOS_UNBATCHED
from repro.core.simulator import demand_vector
from repro.core.sweep import compile_models
from repro.core.transient import (
    _lower_transient,
    build_schedule,
    failover_schedule,
)

ALPHA = calibrate_alpha(PAPER_MULTIPAXOS_UNBATCHED)
CMP = compartmentalized_model(f=1, n_proxy_leaders=10, grid_rows=2,
                              grid_cols=2, n_replicas=4)


# ---------------------------------------------------------------------------
# Steady-state agreement with the other engines
# ---------------------------------------------------------------------------


def test_steady_state_matches_mva_within_5pct():
    """Acceptance bar: the unbatched compartmentalized deployment's
    post-warmup throughput within 5% of exact MVA (the engine simulates
    the exponential FIFO network MVA solves in closed form)."""
    res = transient_throughput(CMP, ALPHA, n_clients=64, seeds=8,
                               n_steps=4000)
    _, x_mva, r_mva = mva_curve(CMP, ALPHA, n_clients_max=64)
    x = float(res.throughput.mean())
    assert x == pytest.approx(float(x_mva[-1]), rel=0.05)
    # mean latency must satisfy Little's law / match MVA's residence time
    assert float(res.latency_mean.mean()) == pytest.approx(
        float(r_mva[-1]), rel=0.10)
    # quantiles are ordered and bracket the mean sensibly
    assert np.all(res.latency_p50 <= res.latency_p99)
    assert float(res.latency_p99.mean()) > float(res.latency_p50.mean())


def test_steady_state_matches_fluid():
    res = transient_throughput(CMP, ALPHA, n_clients=64, seeds=8,
                               n_steps=4000)
    x_fluid = fluid_throughput(CMP, ALPHA, n_clients=64, sim_time=0.05)
    assert float(res.throughput.mean()) == pytest.approx(x_fluid, rel=0.05)


def test_des_is_the_reference_oracle():
    """The numpy/heapq DES (exact FIFO event order) anchors the scan
    engine: same network, same service distribution, same answer."""
    mp = multipaxos_model(f=1)
    x_des, _ = des_throughput(mp, ALPHA, n_clients=64, n_commands=5000,
                              deterministic_service=False)
    res = transient_throughput(mp, ALPHA, n_clients=64, seeds=8,
                               n_steps=4000)
    assert float(res.throughput.mean()) == pytest.approx(x_des, rel=0.10)


def test_des_warmup_removes_coldstart_bias():
    """`done / t` from t=0 folded the ramp-up into the steady-state
    estimate; the post-warmup window must sit orders of magnitude closer
    to the MVA fixed point (deterministic service: exact)."""
    _, x_mva, _ = mva_curve(CMP, ALPHA, n_clients_max=64)
    x_cold, _ = des_throughput(CMP, ALPHA, n_clients=64, n_commands=2000,
                               warmup_commands=0)
    x_warm, _ = des_throughput(CMP, ALPHA, n_clients=64, n_commands=2000)
    err_cold = abs(x_cold - x_mva[-1]) / x_mva[-1]
    err_warm = abs(x_warm - x_mva[-1]) / x_mva[-1]
    assert err_warm < err_cold
    assert err_warm < 1e-6


def test_single_station_deployment():
    """Self-loop routing (one active station) must still satisfy the
    bottleneck law."""
    un = unreplicated_model()
    res = transient_throughput(un, ALPHA, n_clients=16, seeds=8,
                               n_steps=4000)
    assert float(res.throughput.mean()) == pytest.approx(
        un.peak_throughput(ALPHA), rel=0.10)


# ---------------------------------------------------------------------------
# Batched contract + determinism
# ---------------------------------------------------------------------------


def test_batched_sweep_16x8_lanes_one_call():
    """Acceptance bar: >= 16 deployments x >= 8 seeds in one jitted call,
    each row agreeing with its own bottleneck-law peak at saturation."""
    compiled = compile_sweep(SweepSpec(n_proxy_leaders=(2, 4, 6, 10),
                                       grids=((3, 1), (2, 2)),
                                       n_replicas=(2, 4)))
    assert len(compiled) == 16
    res = compiled.transient(ALPHA, n_clients=64, seeds=8, n_steps=3000)
    assert res.throughput.shape == (16, 8)
    assert res.flows.shape == (16, 8, 3000)
    peaks = compiled.peak_throughput(ALPHA)
    x = res.seed_mean_throughput()
    np.testing.assert_allclose(x, peaks, rtol=0.10)


def test_seeded_determinism_and_seed_independence():
    d = demand_vector(CMP) / ALPHA
    a = simulate_transient(d, n_clients=32, seeds=(0, 1, 2, 3), n_steps=2000)
    b = simulate_transient(d, n_clients=32, seeds=(0, 1, 2, 3), n_steps=2000)
    np.testing.assert_array_equal(a.flows, b.flows)
    np.testing.assert_array_equal(a.hist, b.hist)
    # different seeds explore different sample paths...
    c = simulate_transient(d, n_clients=32, seeds=(7, 8, 9, 10), n_steps=2000)
    assert not np.array_equal(a.flows, c.flows)
    # ...but agree on the steady state
    assert float(c.throughput.mean()) == pytest.approx(
        float(a.throughput.mean()), rel=0.10)


def test_deterministic_service_is_seed_invariant():
    d = demand_vector(CMP) / ALPHA
    res = simulate_transient(d, n_clients=32, seeds=4, n_steps=2000,
                             exponential_service=False)
    assert float(res.throughput.std()) == 0.0
    assert float(res.throughput.mean()) == pytest.approx(
        CMP.peak_throughput(ALPHA), rel=0.05)


# ---------------------------------------------------------------------------
# Scripted events
# ---------------------------------------------------------------------------


def test_failover_trace_dips_and_recovers():
    """Leader crash over [0.4, 0.6): throughput must fall below 20% of the
    pre-crash plateau during the outage and recover to >= 85% of it."""
    d = demand_vector(CMP) / ALPHA            # model order: leader is col 0
    sched, bounds = failover_schedule(d, station=0, start=0.4, stop=0.6,
                                      n_steps=5000)
    res = simulate_transient(sched, bounds, n_clients=64, seeds=8,
                             n_steps=5000)
    _, trace = res.throughput_trace(n_windows=20)
    xm = trace.mean(axis=1)[0]                # seed-mean trace
    pre = xm[3:8].mean()                      # post-warmup, pre-crash
    dip = xm[9:11].mean()                     # inside the outage
    post = xm[15:].mean()                     # after recovery
    assert pre > 0
    assert dip < 0.2 * pre
    assert post > 0.85 * pre
    # the stall lives in the tail, not the median
    assert float(res.latency_p99.mean()) > 2.0 * float(res.latency_p50.mean())


def test_scale_up_steps_throughput():
    """Halving the proxy demand mid-run on a proxy-bound deployment must
    roughly double throughput (bottleneck migrates proxy -> leader)."""
    m = compartmentalized_model(f=1, n_proxy_leaders=2, grid_rows=3,
                                grid_cols=1, n_replicas=2)
    assert m.bottleneck()[0] == "proxy"
    d = demand_vector(m) / ALPHA              # model order: proxy is col 1
    sched, bounds = scale_schedule(d, station=1, at=0.5, factor=0.5,
                                   n_steps=5000)
    res = simulate_transient(sched, bounds, n_clients=64, seeds=8,
                             n_steps=5000)
    _, trace = res.throughput_trace(n_windows=20)
    xm = trace.mean(axis=1)[0]
    before, after = xm[4:9].mean(), xm[14:].mean()
    assert after == pytest.approx(2.0 * before, rel=0.15)


def test_zero_demand_window_serves_instead_of_stalling():
    """A window that zeroes an active station's demand means 'free', not
    'crashed': throughput must rise toward the remaining bottleneck, not
    collapse to zero."""
    m = compartmentalized_model(f=1, n_proxy_leaders=2, grid_rows=3,
                                grid_cols=1, n_replicas=2)  # proxy-bound
    d = demand_vector(m) / ALPHA
    sched, bounds = scale_schedule(d, station=1, at=0.5, factor=0.0,
                                   n_steps=5000)
    res = simulate_transient(sched, bounds, n_clients=64, seeds=8,
                             n_steps=5000)
    xm = res.window_throughput(bounds, settle=0.3).mean(axis=1)[0]
    assert xm[1] > 1.5 * xm[0]


def test_step_bounds_must_start_at_zero():
    d = demand_vector(CMP) / ALPHA
    sched = np.repeat(d[None, None, :], 2, axis=0)
    with pytest.raises(ValueError):
        simulate_transient(sched, np.array([100, 300]), n_steps=1000)
    with pytest.raises(ValueError):
        simulate_transient(sched, np.array([0, -5]), n_steps=1000)


def test_window_throughput_respects_bottleneck_caps():
    """Per-window means (transition backlog excluded) must not exceed each
    window's own bottleneck-law cap - the raw trace can, while a faster
    window drains a slower window's queue."""
    m_slow = compartmentalized_model(f=1, n_proxy_leaders=2, grid_rows=3,
                                     grid_cols=1, n_replicas=2)
    m_fast = compartmentalized_model(f=1, n_proxy_leaders=10, grid_rows=2,
                                     grid_cols=2, n_replicas=4)
    windows = [demand_vector(m_slow) / ALPHA, demand_vector(m_fast) / ALPHA]
    sched, bounds = schedule_from_demands(windows, [0.0, 0.5], n_steps=6000)
    res = simulate_transient(sched, bounds, n_clients=128, seeds=8,
                             n_steps=6000)
    xm = res.window_throughput(bounds, settle=0.5).mean(axis=1)[0]
    caps = (m_slow.peak_throughput(ALPHA), m_fast.peak_throughput(ALPHA))
    for x, cap in zip(xm, caps):
        assert x <= cap * 1.05
        assert x >= cap * 0.80


def test_schedule_builders():
    base = np.array([[1.0, 2.0, 0.0]])
    sched, bounds = build_schedule(
        base, [Event(0, 0.25, 0.75, 10.0), Event(1, 0.5, 0.75, 2.0)],
        n_steps=100)
    assert list(bounds) == [0, 25, 50, 75]
    np.testing.assert_allclose(sched[:, 0, 0], [1.0, 10.0, 10.0, 1.0])
    np.testing.assert_allclose(sched[:, 0, 1], [2.0, 2.0, 4.0, 2.0])
    # named stations resolve through the canonical slot table
    s2, _ = build_schedule(np.ones((1, 8)), [Event("leader", 0.0, 1.0, 3.0)],
                           n_steps=10)
    assert s2[0, 0, 1] == 3.0                 # STATION_ORDER[1] == "leader"

    with pytest.raises(ValueError):
        schedule_from_demands([base, base], [0.1, 0.5], n_steps=100)
    with pytest.raises(ValueError):
        schedule_from_demands([base], [0.0, 0.5], n_steps=100)
    sched2, bounds2 = schedule_from_demands([base, 2 * base], [0.0, 0.5],
                                            n_steps=100)
    assert list(bounds2) == [0, 50]
    np.testing.assert_allclose(sched2[1], 2 * base)


# ---------------------------------------------------------------------------
# One completion per lane-step: the scan bins one latency, not every client
# ---------------------------------------------------------------------------


def _per_client_lane(demands_w, step_bounds, dt, entry, nxt, bin_edges, key,
                     n_clients, n_steps, warmup_steps, n_bins, exponential):
    """The scan step with per-client binning: it bins every client's
    latency and adds ``rec`` per client.  It also
    carries the most commands any step completed, which the scan's
    one-latency binning relies on being at most 1."""
    n_windows, k = demands_w.shape
    if exponential:
        draws = jax.random.exponential(key, (n_steps + 1, k))
    else:
        draws = jnp.ones((n_steps + 1, k))
    finishes_at = nxt == k
    arrive_at = jnp.where(finishes_at, entry, nxt)

    def step(state, xs):
        (stage, rank, enter_t, q, work, done, lat_sum, hist, qsum,
         most) = state
        i, draw_i = xs
        t_end = (i + 1).astype(work.dtype) * dt
        w = jnp.searchsorted(step_bounds, i, side="right") - 1
        d_now = demands_w[w]
        rate = jnp.where(d_now > 0, dt / jnp.maximum(d_now, 1e-30), 1e30)
        busy = q > 0
        work = jnp.where(busy, work - rate, work)
        complete = busy & (work <= 0.0)
        dep_here = complete[stage]
        moving = dep_here & (rank == 0)
        fin = moving & finishes_at[stage]
        lat = t_end - enter_t
        rec = fin & (i >= warmup_steps)
        done = done + jnp.sum(rec)
        lat_sum = lat_sum + jnp.sum(jnp.where(rec, lat, 0.0))
        bins = jnp.clip(jnp.searchsorted(bin_edges, lat) - 1, 0, n_bins - 1)
        hist = hist.at[bins].add(rec.astype(jnp.int32))
        most = jnp.maximum(most, jnp.sum(fin))
        dest = arrive_at[stage]
        q_dep = q - complete.astype(q.dtype)
        stage_new = jnp.where(moving, dest, stage)
        enter_new = jnp.where(fin, t_end, enter_t)
        rank_new = jnp.where(
            moving, q_dep[dest],
            rank - (dep_here & (rank > 0)).astype(rank.dtype))
        arrivals = jnp.zeros_like(q).at[arrive_at].add(
            complete.astype(q.dtype))
        q_new = q_dep + arrivals
        qsum = qsum.at[w].add(q_new.astype(qsum.dtype))
        fresh = (complete & (q_new > 0)) | (~busy & (arrivals > 0))
        work_new = jnp.where(
            fresh, draw_i + jnp.where(complete, work, 0.0), work)
        return ((stage_new, rank_new, enter_new, q_new, work_new, done,
                 lat_sum, hist, qsum, most), jnp.sum(fin).astype(jnp.int32))

    state0 = (jnp.full((n_clients,), entry, dtype=jnp.int32),
              jnp.arange(n_clients, dtype=jnp.int32),
              jnp.zeros((n_clients,)),
              jnp.zeros((k,), jnp.int32).at[entry].add(n_clients),
              jnp.zeros((k,)).at[entry].set(draws[0, entry]),
              jnp.asarray(0, jnp.int32), jnp.asarray(0.0),
              jnp.zeros((n_bins,), jnp.int32), jnp.zeros((n_windows, k)),
              jnp.asarray(0, jnp.int32))
    xs = (jnp.arange(n_steps, dtype=jnp.int32), draws[1:])
    (*_, done, lat_sum, hist, qsum, most), flows = jax.lax.scan(
        step, state0, xs)
    return flows, done, lat_sum, hist, qsum, most


N_LANE_STEPS = 1200
LANE_RUN = dict(n_clients=32, seeds=(3, 11), n_steps=N_LANE_STEPS)


def _lane_schedules():
    """Four deployments in canonical slots (tandems of four, two and one
    active stations; an inactive leader in the unreplicated row) under
    the three kinds of schedule the scan's callers build."""
    base = compile_models([
        CMP, multipaxos_model(f=1), unreplicated_model(),
        compartmentalized_model(f=1, n_proxy_leaders=2, grid_rows=3,
                                grid_cols=1, n_replicas=2),
    ]).demands(Workload(f_write=1.0)) / ALPHA
    n = N_LANE_STEPS
    return {
        "failover": failover_schedule(base, station="leader", start=0.4,
                                      stop=0.6, n_steps=n),
        "scale": scale_schedule(base, station="proxy", at=0.5, factor=0.5,
                                n_steps=n),
        "zero_demand": build_schedule(
            base, [Event("proxy", 0.3, 0.7, 0.0),
                   Event("replica", 0.3, 0.7, 0.0)], n_steps=n),
    }


@functools.lru_cache(maxsize=None)
def _per_client_run(schedule, exponential):
    """Every (deployment, seed) lane of ``_per_client_lane`` on the inputs
    ``simulate_transient`` lowers for ``LANE_RUN``; numpy arrays
    [M, S, ...]."""
    sched, bounds = _lane_schedules()[schedule]
    d, step_bounds, dt, entry, nxt, edges, seeds_arr, warmup = (
        _lower_transient(sched, bounds, N_LANE_STEPS, None, 4.0, 96,
                         LANE_RUN["seeds"], 0.25))
    keys = jax.vmap(lambda s: jax.random.fold_in(jax.random.key(0), s))(
        seeds_arr)

    @jax.jit
    def run(d_w, dt_m, entry_m, nxt_m, edges_m):
        def lanes(d_m, dt_1, entry_1, nxt_1, edges_1):
            return jax.vmap(lambda key: _per_client_lane(
                d_m, step_bounds, dt_1, entry_1, nxt_1, edges_1, key,
                LANE_RUN["n_clients"], N_LANE_STEPS, warmup, 96,
                exponential))(keys)
        return jax.vmap(lanes, in_axes=(1, 0, 0, 0, 0))(
            d_w, dt_m, entry_m, nxt_m, edges_m)

    return [np.asarray(x) for x in run(d, dt, entry, nxt, edges)]


LANE_CASES = pytest.mark.parametrize(
    "schedule,exponential",
    [(s, e) for s in ("failover", "scale", "zero_demand")
     for e in (True, False)])


@LANE_CASES
def test_one_latency_binning_matches_per_client_binning(schedule,
                                                        exponential):
    """The scan bins the one latency a lane completes per step; on every
    schedule kind its histogram, completions, mean latency and flows are
    bit for bit those of binning every client."""
    sched, bounds = _lane_schedules()[schedule]
    res = simulate_transient(sched, bounds, exponential_service=exponential,
                             **LANE_RUN)
    flows, done, lat_sum, hist, _, _ = _per_client_run(schedule, exponential)
    np.testing.assert_array_equal(res.hist, hist)
    np.testing.assert_array_equal(res.completed, done)
    np.testing.assert_array_equal(res.latency_mean,
                                  lat_sum / np.maximum(done, 1))
    np.testing.assert_array_equal(res.flows, flows)
    np.testing.assert_array_equal(res.hist.sum(axis=2), res.completed)
    assert res.completed.min() > 0


@LANE_CASES
def test_a_lane_completes_at_most_one_command_per_step(schedule,
                                                       exponential):
    """The invariant the scan's binning rests on: a tandem's last station
    is FIFO, so no step completes two commands in one lane.  A routing
    that broke it would make the scan undercount ``hist``."""
    *_, most = _per_client_run(schedule, exponential)
    assert most.max() == 1
