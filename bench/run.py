"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
last lines of standard error are the numbers that decided ``correct``,
each beside its limit.  Exits non-zero, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
