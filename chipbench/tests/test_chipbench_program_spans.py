"""The readers of the program's own spans and counters, at a tiny size on
the CPU: each gives a number in each of its cells after two answers, none
where the program keeps no such record, and the check adds no answer of
the program's to what they read."""
import sys
from types import SimpleNamespace

import pytest

import repro.core
from chipbench import harness
from repro.core import tracing

CELLS = ["fig28-transient-w", "fig30-execute-r60", "fig30-execute-r90",
         "fig28-mva-w"]
ROOTS = ("repro.mva", "repro.transient", "repro.execute")
READERS = ("program_host_s_per_answer", "pull_bytes_per_answer",
           "execute_probe_s_per_answer", "execute_probe_runs_per_answer",
           "execute_host_reduce_s_per_answer")


def _program_metrics(spec, cell):
    return [m for m in harness.cell_metrics(spec, cell, "per_layer")
            if m["name"] in READERS]


def _answers(root, cell, n=2):
    c = harness.find_cell(root, cell)
    engine = c.engine.Engine(c.config, c.traffic, 2_600_000_029)
    kept = [engine.keep(harness._answer(engine, i)) for i in range(n)]
    return c, engine, kept


def _read(root, metric, engine, answers):
    reader = harness._load_module(root / harness.METRIC_DIR
                                  / f"{metric['name']}.py")
    return reader.read(SimpleNamespace(engine=engine, answers=answers))


@pytest.mark.parametrize("cell", CELLS)
def test_each_reader_reads_its_cells(tiny_root, cell):
    c, engine, _ = _answers(tiny_root, cell)
    metrics = _program_metrics(c.spec, cell)
    assert {"program_host_s_per_answer", "pull_bytes_per_answer"} <= {
        m["name"] for m in metrics}
    values = {m["name"]: _read(tiny_root, m, engine, 2) for m in metrics}
    assert all(isinstance(v, float) and v >= 0 for v in values.values())
    assert values["program_host_s_per_answer"] > 0
    assert values["pull_bytes_per_answer"] > 0
    if "execute_probe_runs_per_answer" in values:
        # a mixed workload probes every config twice: writes, then the mix
        assert values["execute_probe_runs_per_answer"] == 2 * len(
            c.config["deployments"])
        assert values["execute_probe_s_per_answer"] > 0
        assert values["execute_host_reduce_s_per_answer"] > 0
    # more answers than the program recorded: nothing to read
    recorded = len(tracing.recent("repro." + c.traffic["engine"],
                                  tracing.KEEP))
    assert all(_read(tiny_root, m, engine, recorded + 1) is None
               for m in metrics)


def test_readers_read_nothing_from_a_program_without_spans(
        tiny_root, monkeypatch):
    c, engine, _ = _answers(tiny_root, "fig28-mva-w")
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    for m in _program_metrics(c.spec, "fig28-mva-w"):
        assert _read(tiny_root, m, engine, 2) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_check_opens_no_root_of_the_program(tiny_root, cell):
    _, engine, kept = _answers(tiny_root, cell)
    before = {name: tracing.recent(name, tracing.KEEP) for name in ROOTS}
    engine.check(kept)
    after = {name: tracing.recent(name, tracing.KEEP) for name in ROOTS}
    assert after == before
