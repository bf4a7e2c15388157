"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for. Exits non-zero, and prints no result, where JAX finds no
TPU or too few chips. The last line of standard output is the result
object; the last lines of standard error give each number the check
compared, beside its limit. See ``harness.py`` for the layout of this
directory.
"""

import sys
import time

T_PROCESS = time.perf_counter()

from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=T_PROCESS))
