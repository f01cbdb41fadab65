"""The paper's Fig. 29a ablation on both planes: one quorum system per
acceptor shape (a grid, or the 2f+1 majority column), probes of the
fused MultiPaxos and majority clusters that equal the table exactly, and
the execute scan's step counters."""
import numpy as np
import pytest

from repro.core import (
    SweepSpec,
    Workload,
    ablation_steps,
    calibrate_alpha,
    compartmentalized_model,
    execute_configs,
    grids_under,
    majority_grid,
    multipaxos_model,
    run_variant,
    tracing,
    validate_batched,
)
from repro.core.batched_execution import SCAN_CHUNK


def _majority(p, n):
    return dict(variant="compartmentalized", f=1, n_proxy_leaders=p,
                grid_rows=3, grid_cols=1, quorums="majority", n_replicas=n)


# the eight rows of Fig. 29a, as ablation_steps() has them
ROWS = ([dict(variant="multipaxos", f=1)]
        + [_majority(p, n) for p, n in
           ((2, 2), (3, 2), (5, 2), (7, 2), (7, 3), (10, 3))]
        + [dict(variant="compartmentalized", f=1, n_proxy_leaders=10,
                grid_rows=2, grid_cols=2, n_replicas=4)])


def _model(cfg):
    knobs = {k: v for k, v in cfg.items() if k != "variant"}
    if cfg["variant"] == "multipaxos":
        return multipaxos_model(**knobs)
    return compartmentalized_model(**knobs)


def test_rows_are_the_ablation_steps():
    for cfg, (_, model) in zip(ROWS, ablation_steps(), strict=True):
        assert _model(cfg).demands() == model.demands()


@pytest.mark.parametrize("probe_seed", [7919, 11, 12345])
@pytest.mark.parametrize("row", range(len(ROWS)))
def test_fig29a_row_probes_equal_the_table(row, probe_seed):
    cfg = ROWS[row]
    rep = validate_batched(cfg["variant"], cfg, Workload(f_write=1.0),
                           n_commands=48, seeds=2, probe_seed=probe_seed)
    assert rep.passed, str(rep)
    for r in rep.rows:
        assert abs(r.measured - r.predicted) <= 1e-9, str(rep)


def test_multipaxos_table_and_anchor_are_unchanged():
    leader, follower = multipaxos_model(f=1).stations
    assert leader.demand_write == 1 + 2 + 2 + 3 + 1.0 / 3
    assert follower.demand_write == 2.0 * 2 / 3 + 1 + 1.0 / 3
    assert calibrate_alpha() == 25_000.0 * leader.demand_write


@pytest.mark.parametrize("read_fraction", [0.0, 0.6])
@pytest.mark.parametrize("p,n", [(2, 2), (7, 3)])
def test_majority_table_equals_the_wire(p, n, read_fraction):
    cfg = _majority(p, n)
    for seed in (7919, 11):
        t = run_variant("compartmentalized", cfg,
                        Workload.read_mix(read_fraction), n_commands=60,
                        seed=seed)
        want = _model(cfg).demands(Workload(f_write=t.n_writes / 60))
        assert set(t.station_msgs) == {s for s, d in want.items() if d > 0}
        for station, got in t.station_msgs.items():
            assert got == pytest.approx(want[station], abs=1e-9), station


@pytest.mark.parametrize("f", [0, 1])
def test_three_by_one_is_one_system_on_both_planes(f):
    cfg = dict(f=f, n_proxy_leaders=2, grid_rows=3, grid_cols=1,
               n_replicas=2)
    table = compartmentalized_model(**cfg).demands()
    assert table["acceptor"] == 2.0      # a 3x1 grid: the column of three
    if f == 1:
        with pytest.raises(ValueError, match="does not tolerate f=1"):
            run_variant("compartmentalized", cfg, Workload(f_write=1.0),
                        n_commands=12)
        return
    t = run_variant("compartmentalized", cfg, Workload(f_write=1.0),
                    n_commands=48, seed=11)
    assert t.station_msgs == pytest.approx(
        {s: d for s, d in table.items() if d > 0}, abs=1e-12)


def test_majority_spans_the_column_only():
    with pytest.raises(ValueError, match="majority quorums span"):
        compartmentalized_model(f=1, grid_rows=2, grid_cols=2,
                                quorums="majority")
    with pytest.raises(ValueError, match="quorums must be"):
        compartmentalized_model(quorums="flexible")


def test_grids_knob_carries_the_majority_column():
    assert majority_grid(1) == (3, 1, "majority")
    grids = grids_under(6, 1)
    assert grids[0] == majority_grid(1) and (3, 1) not in grids
    configs = list(SweepSpec(grids=(majority_grid(1), (2, 2))).configs())
    assert [c.get("quorums") for c in configs] == ["majority", None]
    assert [(c["grid_rows"], c["grid_cols"]) for c in configs] == [(3, 1),
                                                                    (2, 2)]


@pytest.mark.parametrize("read_fraction", [0.0, 0.6])
def test_execute_counts_lane_and_scan_steps(read_fraction):
    configs = [ROWS[1], ROWS[-1]]
    res = execute_configs(configs, workload=Workload.read_mix(read_fraction),
                          n_commands=24, seeds=3, n_clients=4, probe_n=12)
    counts = tracing.recent("repro.execute", 1)[0].counts
    steps = np.rint(res.n_commands / res.throughput / res.dt[:, None])
    assert counts["repro.execute.lane_steps"] == int(steps.sum())
    # the device ran whole chunks up to the last lane's last completion
    ran = SCAN_CHUNK * int(np.ceil(steps.max() / SCAN_CHUNK))
    assert ran < res.n_steps
    assert counts["repro.execute.scan_lane_steps"] == len(configs) * 3 * ran
    assert counts["repro.execute.lane_steps"] < (
        counts["repro.execute.scan_lane_steps"])
