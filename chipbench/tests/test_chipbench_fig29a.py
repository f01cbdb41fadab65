"""The Fig. 29a cell at a tiny size on the CPU: its engine agrees with its
plain reference, the control fails its check, a fault planted in the
system under test comes out not correct, and the readers of the program's
spans and counters read its answers."""
import jax
import ml_dtypes
import pytest

import repro.core.batched_execution as bx
from chipbench import control, harness
from repro.core import tracing
from repro.core.quorums import MajorityQuorums

CELL = "fig29a-execute-w"
READERS = ("program_host_s_per_answer", "pull_bytes_per_answer",
           "execute_probe_s_per_answer", "execute_probe_runs_per_answer",
           "execute_host_reduce_s_per_answer", "execute_drained_step_share")
COUNTERS = ("repro.execute.lane_steps", "repro.execute.scan_lane_steps")


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _answers(root, n=2):
    c = harness.find_cell(root, CELL)
    engine = c.engine.Engine(c.config, c.traffic, 2_600_000_031)
    kept = [engine.keep(harness._answer(engine, i)) for i in range(n)]
    return c, engine, kept


def _read(root, name, engine, answers):
    reader = harness._load_module(root / harness.METRIC_DIR / f"{name}.py")
    return reader.read(type("Ctx", (), dict(engine=engine, answers=answers)))


def test_engine_matches_its_reference(tiny_root):
    _, engine, kept = _answers(tiny_root)
    gaps = engine.check(kept)
    assert not gaps.failed, gaps.rows()
    assert all(r["value"] <= r["limit"] for r in gaps.rows()), gaps.rows()
    assert {r["name"] for r in gaps.rows()} == {
        "throughput", "latency_mean", "histogram", "messages"}


def test_control_fails_the_check(tiny_root):
    (_, sound, ctl), = control.readings(tiny_root, CELL, [3_000_000_037],
                                        require_tpu=False)
    assert all(r["value"] <= r["limit"] for r in sound), sound
    assert [r["name"] for r in ctl if r["value"] > r["limit"]], ctl
    assert control.CONTROL is ml_dtypes.bfloat16


def _three_of_three(self, slot):
    """Every acceptor in every write quorum: the 3x1 grid's column."""
    return frozenset(range(self.n))


def _halved_hist(real):
    def halved(samples, valid, edges):
        return real(samples, valid, edges) // 2
    return halved


def _altered_lane(real):
    def altered(*args, **kwargs):
        fin, lat, done_w, done_r, t_last = real(*args, **kwargs)
        return fin, lat, done_w, done_r, t_last.at[0, 0].multiply(1.001)
    return altered


@pytest.mark.parametrize("fault", ["three_of_three", "halved", "altered"])
def test_fault_is_not_correct(tiny_root, monkeypatch, fault):
    if fault == "three_of_three":
        monkeypatch.setattr(MajorityQuorums, "rotation", _three_of_three)
        failing = "messages"
    elif fault == "halved":
        monkeypatch.setattr(bx, "latency_hist", _halved_hist(bx.latency_hist))
        failing = "histogram"
    else:
        monkeypatch.setattr(bx, "_execute_batch",
                            _altered_lane(bx._execute_batch))
        failing = "throughput"
    result = harness.run(tiny_root, CELL, 2_900_000_041, 0.2, False, 0.0,
                         require_tpu=False)
    assert result["correct"] is False and result["failed"] >= 1
    check = result["checks"][failing]
    assert check["value"] > check["limit"], result["checks"]


def test_each_reader_reads_the_cell(tiny_root):
    c, engine, _ = _answers(tiny_root)
    assert engine.traffic["engine"] == "execute"
    metrics = {m["name"] for m in harness.cell_metrics(c.spec, CELL,
                                                       "per_layer")}
    assert set(READERS) <= metrics
    values = {name: _read(tiny_root, name, engine, 2) for name in READERS}
    assert all(isinstance(v, float) for v in values.values()), values
    # a write-only mix probes each of the eight rows once
    assert values["execute_probe_runs_per_answer"] == 8
    assert 0.0 < values["execute_drained_step_share"] < 100.0
    roots = tracing.recent("repro.execute", 2)
    lane, scan = (sum(r.counts[n] for r in roots) for n in COUNTERS)
    assert values["execute_drained_step_share"] == pytest.approx(
        100.0 * (1.0 - lane / scan), rel=1e-12)


def test_drained_share_reads_nothing_without_the_counters(
        tiny_root, monkeypatch):
    real = tracing.count

    def without(name, n=1):
        if name not in COUNTERS:
            real(name, n)
    monkeypatch.setattr(tracing, "count", without)
    _, engine, _ = _answers(tiny_root)
    assert _read(tiny_root, "execute_drained_step_share", engine, 2) is None
    assert _read(tiny_root, "pull_bytes_per_answer", engine, 2) > 0
