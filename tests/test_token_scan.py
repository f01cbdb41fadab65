"""The token-service step both device scans share (``core/token_scan.py``).

The transient lane and the execute lane run the same step; the execute
lane only adds op classes and budgets.  Driven alike - one window, one
class, budgets no client spends - the two must complete the same commands
on the same steps with the same latencies, bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched_execution as bx
from repro.core import transient as tr
from repro.core.analytical import STATION_ORDER
from repro.core.token_scan import routing

K = len(STATION_ORDER)
N_CLIENTS, N_STEPS, N_BINS = 12, 600, 32
TANDEMS = {"sparse4": (1, 4, 8, 13), "full15": tuple(range(K))}


def _lane_inputs(tandem):
    active = np.zeros((1, K), bool)
    active[0, list(TANDEMS[tandem])] = True
    rng = np.random.default_rng(len(TANDEMS[tandem]))
    d = np.where(active[0], rng.uniform(1e-5, 8e-5, K), 0.0)
    entry, nxt = routing(active)
    dt = float(d.max()) / 4.0
    return (jnp.asarray(d, jnp.float32), jnp.asarray(entry[0]),
            jnp.asarray(nxt[0]), jnp.float32(dt))


@pytest.mark.parametrize("exponential", [True, False],
                         ids=["exponential", "deterministic"])
@pytest.mark.parametrize("tandem", sorted(TANDEMS))
def test_transient_and_execute_lanes_complete_alike(tandem, exponential):
    """Per-step completions equal the transient ``flows``, the counts are
    equal, and the float32 latency sums, taken in step order, are
    equal."""
    d, entry, nxt, dt = _lane_inputs(tandem)
    key = jax.random.fold_in(jax.random.key(0), 5)
    edges = jnp.geomspace(1e-6, 1.0, N_BINS + 1)

    @jax.jit
    def transient_lane():
        return tr._one_lane(d[None], jnp.zeros((1,), jnp.int32), dt, entry,
                            nxt, edges, key, N_CLIENTS, N_STEPS, 0, N_BINS,
                            exponential)

    @jax.jit
    def execute_lane():
        # every op a write, and more ops a client than steps in the run
        cls = jnp.ones((N_CLIENTS, 4), jnp.int32)
        budget = jnp.full((N_CLIENTS,), N_STEPS + 1, jnp.int32)
        state0, step, draws = bx._exec_lane(
            d, 3.0 * d, entry, nxt, cls, budget, dt, key, N_STEPS,
            N_CLIENTS, exponential)
        xs = (jnp.arange(N_STEPS, dtype=jnp.int32), draws)
        state, (fin, lat) = jax.lax.scan(step, state0, xs)
        return fin, lat, state[6], state[7]

    flows, done, lat_sum, _, _ = jax.device_get(transient_lane())
    fin, lat, done_w, done_r = jax.device_get(execute_lane())

    np.testing.assert_array_equal(fin.sum(axis=1), flows)
    assert int(done_w) == int(done) > 0 and int(done_r) == 0
    acc = np.float32(0.0)
    for i in range(N_STEPS):
        acc = np.float32(acc + np.where(fin[i], lat[i], 0.0).sum(
            dtype=np.float32))
    assert acc == lat_sum


@pytest.mark.parametrize("exponential", [True, False],
                         ids=["exponential", "deterministic"])
def test_a_client_parks_after_its_last_op(exponential):
    """Uneven budgets: the clients with one op park while one client runs
    on alone.  Each client completes exactly its budget, and a parked
    client never moves again."""
    d, entry, nxt, dt = _lane_inputs("sparse4")
    budget = jnp.asarray([1, 1, 1, 30], jnp.int32)
    key = jax.random.fold_in(jax.random.key(0), 7)

    @jax.jit
    def lane():
        cls = jnp.ones((4, 30), jnp.int32)
        state0, step, draws = bx._exec_lane(d, d, entry, nxt, cls, budget,
                                            dt, key, N_STEPS, 4, exponential)
        xs = (jnp.arange(N_STEPS, dtype=jnp.int32), draws)
        state, (fin, _) = jax.lax.scan(step, state0, xs)
        return fin, state

    fin, state = jax.device_get(lane())
    np.testing.assert_array_equal(fin.sum(axis=0), np.asarray(budget))
    assert int(state[6]) == int(budget.sum())
    assert int(state[3].sum()) == 0                      # queues empty


def test_routing_rejects_a_row_without_an_active_station():
    active = np.array([[True, False, True], [False, False, False]])
    with pytest.raises(ValueError, match="row 1 has no active station"):
        routing(active)
