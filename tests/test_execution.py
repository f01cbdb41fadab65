"""The generic execution harness (``repro.core.execution``): two planes,
one registry.

* **Parity** - every variant that declares an executable must pass
  ``validate_variant`` (measured per-station msgs/cmd vs its own demand
  table) via the same generic loop the ``msgcount`` benchmark runs, at
  the write-only mix the paper states its tables for *and* at a mixed
  mix exercising the read paths.  The variant list is the registry's,
  not a hand-pin: the ``executable_variant`` fixture (tests/conftest.py)
  iterates ``executable_variants()``, so a newly registered variant
  inherits the whole suite.  Headline counts are pinned exactly:
  compartmentalized leader 2, S-Paxos leader 2 (ids only), unreplicated
  server 2, BPaxos dependency service 2.
* **Linearizability** - the property suite historically exercised
  MultiPaxos only; here Mencius, S-Paxos and CRAQ executions (plus the
  baselines) are checked through the harness's exhaustive Wing-Gong
  verdict on contended workloads across seeds.
* **Calibration** - ``calibrate_alpha(measured=True)`` anchors alpha on
  an *executed* vanilla run.
"""
import pytest

from repro.core import (
    MIXED_50_50,
    STATION_ORDER,
    WRITE_ONLY,
    Workload,
    calibrate_alpha,
    default_config,
    executable_variants,
    registered_variants,
    run_variant,
    validate_variant,
    workload_ops,
)


def test_every_registered_variant_declares_an_executable():
    """Counts and names are derived from the registry, never hand-pinned:
    adding a variant cannot break this test unless it forgets its
    execution plane."""
    names = set(executable_variants())
    assert names == set(registered_variants())
    # the historical eight plus the multi-leader family are all present
    assert {"compartmentalized", "unreplicated", "multipaxos", "mencius",
            "vanilla_mencius", "spaxos", "vanilla_spaxos", "craq",
            "bpaxos", "iss"} <= names


# ---------------------------------------------------------------------------
# Parity: one generic loop, zero per-variant branches
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workload", [WRITE_ONLY, MIXED_50_50],
                         ids=["write_only", "mixed"])
def test_parity_every_executable_variant(executable_variant, workload):
    report = validate_variant(executable_variant, workload=workload,
                              n_commands=48, seed=0)
    assert report.passed, str(report)
    assert report.trace.linearizable


def test_headline_leader_counts_are_exact():
    """Paper section 3.1 / 7: the compartmentalized leader handles exactly
    2 msgs/cmd, the S-Paxos leader exactly 2 id-only msgs/cmd, and the
    vanilla leader >= 3f+4 - measured, not modelled."""
    comp = validate_variant("compartmentalized", workload=Workload(),
                            n_commands=40, seed=0)
    assert comp.row("leader").exact
    assert comp.row("leader").measured == pytest.approx(2.0, abs=1e-9)

    spax = validate_variant("spaxos", workload=Workload(), n_commands=40,
                            seed=0)
    assert spax.row("leader").measured == pytest.approx(2.0, abs=1e-9)

    vanilla = validate_variant("multipaxos", workload=Workload(),
                               n_commands=40, seed=0)
    assert vanilla.row("leader").measured >= 3 * 1 + 4  # 3f+4, f=1

    unrep = validate_variant("unreplicated", workload=Workload(),
                             n_commands=40, seed=0)
    assert unrep.row("server").measured == pytest.approx(2.0, abs=1e-9)

    # the multi-leader family's structural floor: every BPaxos dep-service
    # node sees every command once and replies once - exactly 2 msgs/cmd,
    # the same ceiling the compartmentalized leader has
    bpax = validate_variant("bpaxos", workload=Workload(), n_commands=40,
                            seed=0)
    assert bpax.row("dep_service").exact
    assert bpax.row("dep_service").measured == pytest.approx(2.0, abs=1e-9)


def test_mencius_feedback_reads_skips_off_the_run():
    report = validate_variant("mencius", workload=Workload(), n_commands=45,
                              seed=0)
    assert report.passed, str(report)
    assert report.model_config["announce_interval"] == 1.0
    assert 0.0 < report.model_config["skip_fraction"] < 1.0
    # the user config is untouched: feedback refines the model side only
    assert "skip_fraction" not in report.config


def test_craq_feedback_measures_dirty_forwarding():
    w = Workload(f_write=0.3, skew_p=0.8)
    report = validate_variant("craq", workload=w, n_commands=60, seed=0)
    assert report.passed, str(report)
    forwarded = sum(n.tail_forwards for n in report.trace.deployment.nodes)
    assert forwarded > 0  # hot-key contention really forwards to the tail
    assert report.model_config["skew_p"] > 0.0
    assert report.model_config["dirty_fraction"] == 1.0


def test_trace_buckets_into_canonical_station_slots():
    trace = run_variant("spaxos", n_commands=20, seed=0)
    row = trace.demand_slots()
    assert len(row) == len(STATION_ORDER)
    for station in ("disseminator", "stabilizer", "leader", "proxy",
                    "acceptor", "replica"):
        assert row[STATION_ORDER.index(station)] > 0
    assert row[STATION_ORDER.index("head")] == 0.0  # no chain stations
    assert trace.station_servers["leader"] == 1
    assert trace.deployment.total_messages()["leader"] == 40  # 2/cmd, hoisted


def test_reads_as_writes_baseline_drives_writes_only():
    """The vanilla table has no read path, so its executable declares
    reads_as_writes: even a read-heavy workload executes as writes."""
    trace = run_variant("multipaxos", workload=Workload.read_mix(0.9),
                        n_commands=30, seed=0)
    assert trace.n_reads == 0
    assert trace.n_writes == 30


# ---------------------------------------------------------------------------
# Linearizability across the variant zoo (satellite: property coverage
# beyond MultiPaxos)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_contended_executions_linearizable_exhaustive(executable_variant,
                                                      seed):
    """Small contended runs (hot-key skew, mixed reads/writes, concurrent
    closed-loop clients) checked by the exhaustive Wing-Gong search - the
    ground-truth verdict, inherited by every registered executable (the
    multi-leader family included) through the registry fixture."""
    w = Workload(f_write=0.5, skew_p=0.9)
    trace = run_variant(executable_variant, workload=w, n_commands=10,
                        seed=seed)
    assert trace.checker == "exhaustive"
    assert trace.linearizable, trace.violations


@pytest.mark.parametrize("name", ["mencius", "spaxos", "craq"])
def test_variant_executions_linearizable_under_jitter(name):
    """Message reordering across links must not break linearizability of
    the variant clusters (the harness's checker sees the reordered
    history)."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings, strategies as st

    @given(seed=st.integers(0, 200), f_write=st.sampled_from([0.4, 0.7, 1.0]))
    @settings(max_examples=10, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    def check(seed, f_write):
        trace = run_variant(name, workload=Workload(f_write=f_write,
                                                    skew_p=0.8),
                            n_commands=8, seed=seed, jitter=3.0)
        assert trace.checker == "exhaustive"
        assert trace.linearizable, trace.violations

    check()


def test_larger_histories_fall_back_to_slot_order():
    trace = run_variant("compartmentalized", workload=Workload(f_write=0.5),
                        n_commands=60, seed=0)
    assert trace.checker == "slot_order"
    assert trace.linearizable


def test_slotless_histories_never_get_a_vacuous_verdict():
    """CRAQ responses carry no global log position, so the slot-order
    check would be vacuously true on its histories - large CRAQ runs must
    fall back to the exhaustive verdict instead."""
    trace = run_variant("craq", workload=Workload(f_write=0.5, skew_p=0.5),
                        n_commands=60, seed=0)
    assert trace.checker == "exhaustive"
    assert trace.linearizable


# ---------------------------------------------------------------------------
# Measured calibration + harness edges
# ---------------------------------------------------------------------------


def test_calibrate_alpha_measured_matches_wire_counts():
    """The executed vanilla leader machine handles 3f+4+1 = 8 msgs/cmd
    (client in, 2 p2a out, 2 p2b in, 3 chosen out at 2f+1 replicas) plus
    its replica role's replies to 1/(2f+1) of the commands, as the table
    counts it - so over a multiple of 2f+1 commands the measured anchor
    is the table's, 25k * 8.333."""
    alpha = calibrate_alpha(measured=True, n_commands=30)
    assert alpha == pytest.approx(25_000.0 * (8.0 + 1.0 / 3.0), rel=1e-12)
    assert calibrate_alpha() == pytest.approx(alpha, rel=1e-12)
    with pytest.raises(TypeError, match="model=None"):
        calibrate_alpha(measured=True, model=object())


def test_workload_ops_realize_the_exact_mix():
    ops = workload_ops(Workload(f_write=0.5), 30, seed=4)
    assert sum(1 for op in ops if op[0] == "put") == 15
    ops = workload_ops(Workload(f_write=1.0, skew_p=1.0), 10, seed=0)
    assert all(op[:2] == ("put", "hot") for op in ops)


def test_default_config_is_first_knob_point():
    assert default_config("craq") == {"variant": "craq", "n_nodes": 3}
    assert default_config("mencius")["n_leaders"] == 3


def test_variant_without_executable_is_diagnosed():
    from repro.core import register_variant, temporary_variants
    from repro.core.analytical import vanilla_mencius_model

    with temporary_variants():
        register_variant(name="table_only_proto",
                         factory=vanilla_mencius_model,
                         stations=("server",))
        with pytest.raises(ValueError, match="no execution plane"):
            run_variant("table_only_proto", n_commands=4)
    with pytest.raises(ValueError, match="unknown variant"):
        run_variant("no_such_protocol", n_commands=4)


# ---------------------------------------------------------------------------
# Batched configs on the measured plane (n_batchers > 0)
# ---------------------------------------------------------------------------


BATCHED_CFG = {"f": 1, "n_proxy_leaders": 3, "grid_rows": 2, "grid_cols": 2,
               "n_replicas": 2, "batch_size": 10, "n_batchers": 1,
               "n_unbatchers": 1}


@pytest.mark.parametrize("mix", [WRITE_ONLY, MIXED_50_50],
                         ids=lambda w: f"fw{w.f_write:g}")
def test_batched_config_parity(mix):
    """A compartmentalized config with a real batcher tier passes parity:
    the model feedback replaces the configured batch size with the
    *measured* fill (timer-flushed batches under a small closed-loop
    client population carry ~n_clients commands, not batch_size), so the
    leader check stays exact at any mix."""
    rep = validate_variant("compartmentalized", BATCHED_CFG, workload=mix,
                           n_commands=60, seed=1)
    assert rep.passed, str(rep)
    leader = rep.row("leader")
    assert leader.exact and leader.measured == leader.predicted
    b_eff = rep.model_config["batch_size"]
    assert 1.0 <= b_eff < BATCHED_CFG["batch_size"]
    assert rep.trace.linearizable


def test_batched_feedback_reconciles_with_batch_fill_adapter():
    """The measured amortization and the ``Workload.batch_fill`` adapter
    are the same knob seen from two sides: feeding the measured effective
    batch back as ``batch_size`` must produce the same leader demand as
    keeping ``batch_size`` and lowering the workload's fill hint to
    ``(b_eff - 1) / (B - 1)`` (the inverse of ``effective_batch_size``)."""
    from dataclasses import replace

    from repro.core import variant_spec
    from repro.core.analytical import effective_batch_size

    rep = validate_variant("compartmentalized", BATCHED_CFG,
                           workload=WRITE_ONLY, n_commands=60, seed=1)
    b_eff = rep.model_config["batch_size"]
    B = BATCHED_CFG["batch_size"]
    fill = (b_eff - 1.0) / (B - 1.0)
    spec = variant_spec("compartmentalized")
    via_feedback = spec.build(rep.model_config).demands(WRITE_ONLY)
    hint_cfg = spec.adapt({k: v for k, v in BATCHED_CFG.items()},
                          replace(WRITE_ONLY, batch_fill=fill))
    via_hint = spec.build(hint_cfg).demands(WRITE_ONLY)
    # effective_batch_size rounds to an integer batch; compare through it
    assert hint_cfg["batch_size"] == effective_batch_size(B, fill)
    assert via_hint["leader"] == pytest.approx(via_feedback["leader"],
                                               rel=0.35)
    # and at fill == measured fill the bottleneck-law peaks agree within
    # the same rounding
    assert abs(hint_cfg["batch_size"] - b_eff) <= 0.5 + 1e-9


def test_batched_station_msgs_include_batcher_tier():
    tr = run_variant("compartmentalized", BATCHED_CFG, workload=WRITE_ONLY,
                     n_commands=60, seed=1)
    assert "batcher" in tr.station_msgs
    assert "unbatcher" in tr.station_msgs
    assert tr.station_msgs["batcher"] > 0
