"""The program's spans and counters (``repro.core.tracing``): self-time
arithmetic, re-entry, the bound on kept roots, one root per engine answer
with its children in order, the bytes pulled, the probe runs, and the
spans on a host plane of a profile."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import tracing
from repro.core.analytical import (calibrate_alpha, compartmentalized_model,
                                   multipaxos_model)
from repro.core.api import Workload
from repro.core.batched_execution import execute_configs
from repro.core.simulator import mva_curves_from_demands
from repro.core.sweep import SweepSpec, compile_models, compile_sweep
from repro.core.transient import simulate_transient

ALPHA = calibrate_alpha()
MVA = ["repro.mva.lower", "repro.mva.dispatch", "repro.mva.wait",
       "repro.mva.pull"]
TRANSIENT = ["repro.transient.lower", "repro.transient.dispatch",
             "repro.transient.wait", "repro.transient.pull",
             "repro.transient.reduce"]
EXECUTE_TAIL = ["repro.execute.streams", "repro.execute.lower",
                "repro.execute.dispatch", "repro.execute.wait",
                "repro.execute.pull", "repro.execute.hist",
                "repro.execute.wait", "repro.execute.pull",
                "repro.execute.reduce"]


class FakeClock:
    def __init__(self) -> None:
        self.ns = 0

    def __call__(self) -> int:
        return self.ns

    def advance(self, ns: int) -> None:
        self.ns += ns


@pytest.fixture
def clock(monkeypatch):
    fake = FakeClock()
    monkeypatch.setattr(tracing, "_clock", fake)
    return fake


def new_roots(call):
    """Run ``call`` and return it with the roots it finished."""
    before = {id(r) for r in tracing._finished}
    out = call()
    return out, [r for r in tracing._finished if id(r) not in before]


# -- the record ---------------------------------------------------------------

def test_self_time_is_duration_less_children(clock):
    with tracing.span("t.root") as root:
        clock.advance(5)
        with tracing.span("t.root.a") as a:
            assert a.root is root.root
            clock.advance(7)
            with tracing.span("t.root.b") as b:
                assert b.root.id == root.root.id
                clock.advance(11)
                tracing.count("t.c", 2)
            clock.advance(13)
        with tracing.span("t.root.a"):
            clock.advance(17)
        clock.advance(3)
        tracing.count("t.c")
    (r,) = tracing.recent("t.root", 1)
    assert r is root.root
    assert r.duration_s == pytest.approx(56e-9)
    assert r.self_s("t.root") == pytest.approx(8e-9)
    assert r.self_s("t.root.a") == pytest.approx((7 + 13 + 17) * 1e-9)
    assert r.self_s("t.root.b") == pytest.approx(11e-9)
    assert r.self_s("t.other") == 0.0
    assert r.spans == ["t.root.b", "t.root.a", "t.root.a"]
    assert r.counts == {"t.c": 3}


def test_a_span_named_like_the_open_one_is_that_span(clock):
    @tracing.span("t.entry")
    def inner():
        clock.advance(4)

    @tracing.span("t.entry")
    def outer():
        clock.advance(2)
        inner()

    _, roots = new_roots(outer)
    assert [r.name for r in roots] == ["t.entry"]
    assert roots[0].duration_s == pytest.approx(6e-9)
    assert roots[0].self_s("t.entry") == pytest.approx(6e-9)
    assert roots[0].spans == []


def test_count_outside_a_root_records_nothing(clock):
    _, roots = new_roots(lambda: tracing.count("t.c", 5))
    assert roots == []


def test_recent_keeps_the_last_roots_and_at_most_keep(clock):
    for i in range(tracing.KEEP + 6):
        with tracing.span("t.bound"):
            clock.advance(i)
    assert tracing._finished.maxlen == tracing.KEEP == 1024
    kept = tracing.recent("t.bound", 10 * tracing.KEEP)
    assert len(kept) == tracing.KEEP
    last = tracing.recent("t.bound", 3)
    assert [r.duration_s * 1e9 for r in last] == pytest.approx(
        [tracing.KEEP + 3, tracing.KEEP + 4, tracing.KEEP + 5])
    assert tracing.recent("t.bound", 0) == []


# -- the engines --------------------------------------------------------------

@pytest.fixture(scope="module")
def grid():
    return compile_models([multipaxos_model(), compartmentalized_model()])


@pytest.fixture(scope="module")
def configs():
    return list(compile_sweep(
        SweepSpec(variants=("compartmentalized",))).configs)[:1] * 2


def _one_root(call, name):
    out, roots = new_roots(call)
    assert [r.name for r in roots] == [name]
    return out, roots[0]


def test_compiled_mva_makes_one_root(grid):
    (_, x, r), root = _one_root(lambda: grid.mva(ALPHA, n_clients_max=8),
                                "repro.mva")
    assert root.spans == MVA
    assert root.counts[tracing.PULL_BYTES] == x.nbytes + r.nbytes > 0


def test_mva_from_demands_makes_one_root(grid):
    d = grid.demands() / ALPHA
    (_, x, r), root = _one_root(lambda: mva_curves_from_demands(d, 8),
                                "repro.mva")
    assert root.spans == MVA[1:]
    assert root.counts[tracing.PULL_BYTES] == x.nbytes + r.nbytes


def _transient_bytes(res):
    lat_sum = res.completed.size * np.dtype(np.float32).itemsize
    return (res.flows.nbytes + res.completed.nbytes + res.hist.nbytes
            + res.queue_sums.nbytes + lat_sum)


def test_compiled_transient_makes_one_root(grid):
    res, root = _one_root(lambda: grid.transient(
        ALPHA, n_clients=4, seeds=2, n_steps=64), "repro.transient")
    assert root.spans == TRANSIENT[:1] + TRANSIENT
    assert root.counts[tracing.PULL_BYTES] == _transient_bytes(res)


def test_simulate_transient_makes_one_root(grid):
    d = grid.demands() / ALPHA
    res, root = _one_root(lambda: simulate_transient(
        d, n_clients=4, seeds=2, n_steps=64), "repro.transient")
    assert root.spans == TRANSIENT
    assert root.counts[tracing.PULL_BYTES] == _transient_bytes(res)


def _execute_bytes(res):
    m, s = res.completed.shape
    width = -(-res.n_commands // res.n_clients)
    # a float32 slot per op of each client; done_w, done_r, t_last per lane
    return res.hist.nbytes + m * s * res.n_clients * width * 4 + m * s * 4 * 3


@pytest.mark.parametrize("mix,runs", [(0.5, 2), (0.0, 1)])
def test_execute_configs_makes_one_root(configs, mix, runs):
    res, root = _one_root(lambda: execute_configs(
        configs, workload=Workload.read_mix(mix), n_commands=8, seeds=2,
        n_clients=2, alpha=ALPHA), "repro.execute")
    probes = ["repro.execute.probe"] * len(configs)
    assert root.spans == ["repro.execute.lower"] + probes + EXECUTE_TAIL
    assert root.counts["repro.execute.probe_runs"] == runs * len(configs)
    assert root.counts[tracing.PULL_BYTES] == _execute_bytes(res)


def test_compiled_execute_makes_one_root():
    sweep = compile_sweep(SweepSpec(variants=("compartmentalized",))).subset(
        [0])
    res, root = _one_root(lambda: sweep.execute(
        workload=Workload.read_mix(0.5), n_commands=8, seeds=2, n_clients=2,
        alpha=ALPHA), "repro.execute")
    assert root.spans == (["repro.execute.lower", "repro.execute.probe"]
                          + EXECUTE_TAIL)
    assert root.counts["repro.execute.probe_runs"] == 2
    assert root.counts[tracing.PULL_BYTES] == _execute_bytes(res)


# -- the profile --------------------------------------------------------------

def _host_spans(directory):
    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


@pytest.mark.parametrize("engine", ["mva", "transient"])
def test_spans_sit_on_a_host_plane_of_the_profile(grid, tmp_path, engine):
    call = {"mva": lambda: grid.mva(ALPHA, n_clients_max=8),
            "transient": lambda: grid.transient(ALPHA, n_clients=4, seeds=2,
                                                n_steps=64)}[engine]
    call()                                   # compile outside the profile
    with jax.profiler.trace(str(tmp_path)):
        _, root = _one_root(call, f"repro.{engine}")
    spans = sorted(_host_spans(str(tmp_path)), key=lambda s: (s[1], -s[2]))
    (name, lo, hi), *children = spans
    assert name == root.name
    assert [c[0] for c in children] == root.spans
    end = lo
    for _, start, stop in children:         # in order, inside the root
        assert end <= start <= stop <= hi
        end = stop
