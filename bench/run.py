"""Benchmark entry point: one run of one cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints the run's result as the last line of standard output, and each
number that decides ``correct`` beside its limit as the last lines of
standard error.  Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for, where Pallas would run in
interpret mode, or where the program (``src/``) is not beside it.
"""

import time

T_START = time.perf_counter()

import os                       # noqa: E402
import sys                      # noqa: E402
from pathlib import Path        # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START, ROOT))
