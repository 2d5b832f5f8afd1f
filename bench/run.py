"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--rehearse`` runs the cell's small preset on the CPU (Pallas kernels in
interpret mode); without it a run that finds no TPU exits non-zero and
prints no result.  See ``bench/harness.py``.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(_BENCH), "src"))
sys.path.insert(0, _BENCH)

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
