"""Readings that set the limits of a cell's check; not part of a run.

    python3 chipbench/control.py --workload <name> --seeds <n> [--first <seed>]

For each seed, in one process: the cell's set-up and as many answers at
its own size as a run checks, then two comparisons of the same answers
with the plain reference.  In one the engine's outputs are compared (the
lower reading); in the other the reference itself, computed in bfloat16,
stands in the engine's place (the control, the upper reading).  Prints
one JSON line per seed and, last, each number's largest engine reading
and smallest control reading.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import ml_dtypes  # noqa: E402

from chipbench import harness  # noqa: E402

CONTROL = ml_dtypes.bfloat16


def readings(root: Path, workload: str, seeds, require_tpu: bool = True):
    """Yield (seed, engine rows, control rows) for each seed."""
    cell = harness.find_cell(root, workload)
    harness.devices_for(cell.chips, harness._load_json(root / harness.PEAKS),
                        require_tpu)
    t = cell.traffic
    n = max(t["check"]["answers"], t.get("warmup_answers", 1))
    for seed in seeds:
        engine = cell.engine.Engine(cell.config, t, seed)
        kept = [engine.keep(harness._answer(engine, i)) for i in range(n)]
        yield (seed, engine.check(kept).rows(),
               engine.check(kept, control=CONTROL).rows())


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first", type=int, default=3_000_000_000)
    args = p.parse_args(argv)
    harness.enable_compile_cache(ROOT)
    lower, upper = {}, {}
    try:
        seeds = range(args.first, args.first + args.seeds)
        for seed, sound, control in readings(ROOT, args.workload, seeds):
            for r in sound:
                lower[r["name"]] = max(lower.get(r["name"], 0.0), r["value"])
            for r in control:
                upper[r["name"]] = min(upper.get(r["name"], float("inf")),
                                       r["value"])
            print(json.dumps({
                "seed": seed,
                "engine": {r["name"]: r["value"] for r in sound},
                "control": {r["name"]: r["value"] for r in control}}),
                flush=True)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "engine_max": lower, "control_min": upper,
                      "seconds": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
