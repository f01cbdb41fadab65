"""BENCHMARK.json against the benchmark's contract, and a cell added as
files alone."""
import json
import re
from pathlib import Path

import pytest

from chipbench import harness

REPO = Path(__file__).resolve().parents[2]

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_exact_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_and_units_use_allowed_characters():
    names = []
    for c in SPEC["configs"]:
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        names.append(w["name"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))


def test_metric_entries_and_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert (REPO / harness.METRIC_DIR / f"{m['name']}.py").is_file()
        assert m["layer"] and "\n" not in m["layer"]


def test_every_cell_resolves_to_its_files():
    used = set()
    for w in SPEC["workloads"]:
        cell = harness.find_cell(REPO, w["name"])
        assert hasattr(cell.engine, "Engine")
        used.add(w["config"])
        assert set(cell.traffic["limits"]), w["name"]
    assert used == {c["name"] for c in SPEC["configs"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith("chipbench/") for f in files)


def test_a_cell_added_as_files_is_found_and_runs(tiny_root):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    traffic = json.loads(
        (tiny_root / "chipbench/traffic/mva-w.json").read_text())
    traffic["mix"] = {"read_fraction": 0.5}
    (tiny_root / "chipbench/traffic/mva-r50.json").write_text(
        json.dumps(traffic))
    spec["workloads"].append({"name": "fig30-mva-r50", "config": "fig30-f1",
                              "traffic": "mva-r50", "chips": 1,
                              "why": "half reads"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    result = harness.run(tiny_root, "fig30-mva-r50", 7, 0.2, False, 0.0,
                         require_tpu=False)
    assert result["correct"] and result["attempted"] >= 1
    assert set(result["metrics"]) == {"setup_s", "answers_per_s",
                                      "device_peak_bytes"}
    assert list(result)[-1] == "checks"


def test_unknown_workload_is_an_error(tiny_root):
    with pytest.raises(KeyError):
        harness.find_cell(tiny_root, "no-such-cell")
