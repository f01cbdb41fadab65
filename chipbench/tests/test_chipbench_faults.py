"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the system under test, at the place it would
occur, and the rest of a run (set-up, window, check) is driven with the
harness's look for a chip skipped.  There is no exchange between chips
to leave out: every cell runs on one chip.
"""
import jax
import jax.numpy as jnp
import pytest

import repro.core.batched_execution as bx
import repro.core.simulator as sim
import repro.core.transient as tr
from chipbench import harness


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _run(root, cell):
    return harness.run(root, cell, 2_900_000_023, 0.2, False, 0.0,
                       require_tpu=False)


def _first_half(fn, seed_arg):
    """``fn`` over the first half of the seeds; the rest repeat it."""
    def half(*args, **kwargs):
        args = list(args)
        h = args[seed_arg].shape[0] // 2
        args[seed_arg] = args[seed_arg][:h]
        out = fn(*args, **kwargs)
        return tuple(jnp.concatenate([o, o], axis=1) for o in out)
    return half


# -- the token scan --------------------------------------------------------

def _frozen_lane(demands_w, step_bounds, dt, entry, nxt, bin_edges, key,
                 n_clients, n_steps, warmup_steps, n_bins, exponential):
    """A scan whose step returns its state unchanged: the initial state."""
    n_windows, k = demands_w.shape
    return (jnp.zeros((n_steps,), jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray(0.0), jnp.zeros((n_bins,), jnp.int32),
            jnp.zeros((n_windows, k)))


def _altered_transient(real):
    def altered(*args, **kwargs):
        flows, done, lat_sum, hist, qsum = real(*args, **kwargs)
        return flows, done, lat_sum, hist.at[0, 0, 0].add(1), qsum
    return altered


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_transient_fault_is_not_correct(tiny_root, monkeypatch, fault):
    if fault == "unchanged":
        monkeypatch.setattr(tr, "_one_lane", _frozen_lane)
    elif fault == "half":
        monkeypatch.setattr(tr, "_transient_batch",
                            _first_half(tr._transient_batch, 6))
    else:
        monkeypatch.setattr(tr, "_transient_batch",
                            _altered_transient(tr._transient_batch))
    result = _run(tiny_root, "fig28-transient-w")
    assert result["correct"] is False and result["failed"] >= 1


# -- the execution scan ----------------------------------------------------

def _frozen_exec_lane(d_w, d_r, entry, nxt, cls_stream, budget, dt, key,
                      n_steps, n_clients, exponential):
    zero = jnp.asarray(0, jnp.int32)
    return (jnp.zeros((n_steps, n_clients), bool),
            jnp.zeros((n_steps, n_clients)), zero, zero, jnp.asarray(0.0))


def _half_configs(real):
    """The execution scan over the first half of the configs; the rest
    repeat it.  (Its seeds would not do: under deterministic service a
    write-only lane is the same on every seed.)"""
    def half(d_w, d_r, entry, nxt, cls, budget, dt, seeds, **kwargs):
        h = -(-d_w.shape[0] // 2)
        out = real(d_w[:h], d_r[:h], entry[:h], nxt[:h], cls[:h], budget[:h],
                   dt[:h], seeds, **kwargs)
        pick = jnp.arange(d_w.shape[0]) % h
        return tuple(o[pick] for o in out)
    return half


def _altered_hist(real):
    def altered(samples, valid, edges):
        return real(samples, valid, edges).at[0, 0].add(1)
    return altered


@pytest.mark.parametrize("cell", ["fig30-execute-r60", "fig30-execute-r90"])
@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_execute_fault_is_not_correct(tiny_root, monkeypatch, cell, fault):
    if fault == "unchanged":
        # the engine's own guard refuses lanes that drained nothing, so
        # the run ends without a result
        monkeypatch.setattr(bx, "_one_exec_lane", _frozen_exec_lane)
        with pytest.raises(RuntimeError, match="drained"):
            _run(tiny_root, cell)
        return
    if fault == "half":
        monkeypatch.setattr(bx, "_execute_batch",
                            _half_configs(bx._execute_batch))
    else:
        monkeypatch.setattr(bx, "latency_hist", _altered_hist(bx.latency_hist))
    result = _run(tiny_root, cell)
    assert result["correct"] is False and result["failed"] >= 1


# -- the MVA solve ---------------------------------------------------------

def _frozen_mva(demands, think, n_max):
    """The recursion with its queue state never carried forward."""
    def step(q, n):
        r_k = demands * (1.0 + q)
        r = jnp.sum(r_k)
        return q, (n / (think + r), r)
    ns = jnp.arange(1, n_max + 1, dtype=demands.dtype)
    return jax.lax.scan(step, jnp.zeros_like(demands), ns)[1]


def _half_rows(real):
    def half(demands, think, n_max):
        h = -(-demands.shape[0] // 2)
        xs, rs = real(demands[:h], think, n_max=n_max)
        pick = jnp.arange(demands.shape[0]) % h
        return xs[pick], rs[pick]
    return half


def _altered_mva(real):
    def altered(demands, think, n_max):
        xs, rs = real(demands, think, n_max=n_max)
        return xs.at[0, 10].multiply(1.001), rs
    return altered


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_mva_fault_is_not_correct(tiny_root, monkeypatch, fault):
    if fault == "unchanged":
        monkeypatch.setattr(sim, "_mva_scan_impl", _frozen_mva)
    elif fault == "half":
        monkeypatch.setattr(sim, "_mva_scan_batch",
                            _half_rows(sim._mva_scan_batch))
    else:
        monkeypatch.setattr(sim, "_mva_scan_batch",
                            _altered_mva(sim._mva_scan_batch))
    result = _run(tiny_root, "fig28-mva-w")
    assert result["correct"] is False and result["failed"] >= 1
