"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  Without a TPU it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START, ROOT))
