"""Benchmark entry point; run from the root of a checkout::

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It needs a TPU: without one it exits non-zero and prints no result.  The
last line of standard output is the run's JSON result (see
``bench/harness.py`` and PERF.md).
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t0=T0))
