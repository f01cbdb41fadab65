"""Fixtures of the benchmark's own tests: a copy of the benchmark with
every traffic mix shrunk to a size the CPU runs in seconds."""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for path in (str(REPO / "src"), str(REPO)):
    if path not in sys.path:
        sys.path.insert(0, path)

TINY = {
    "transient": dict(clients=12, steps=300, lane_seeds=2,
                      check={"answers": 1, "lanes": 10}),
    "execute": dict(clients=6, commands=36, lane_seeds=2, probe_n=12,
                    check={"answers": 1, "lanes": 18}),
    "mva": dict(clients=48, check={"answers": 4}),
}


def shrink(root: Path) -> Path:
    """Shrink every traffic file under ``root`` to its engine's tiny size."""
    for path in (root / "chipbench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        t.update(TINY[t["engine"]])
        path.write_text(json.dumps(t))
    return root


@pytest.fixture
def tiny_root(tmp_path: Path) -> Path:
    """A checkout of the benchmark alone, with tiny traffic."""
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    return shrink(tmp_path)
