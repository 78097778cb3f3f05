"""python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The benchmark's command: one cell, one process, no child, on the machine
it is started on. Everything it does is in chipbench/harness.py; this file
only takes the clock first, so that `setup_s` holds the imports too."""
import time; _T0 = time.perf_counter()  # noqa: E702 -- before any other import

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], _T0))
