#!/usr/bin/env python3
"""Entry point of the benchmark, run from the repository's root:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>
"""
import time

T0 = time.monotonic()  # set-up is timed from here, before JAX is imported

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
