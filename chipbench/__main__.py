"""`python3 -m chipbench --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`: one run of one cell, in one process that holds the
chip. The last line of standard output is the result."""

import time

_T0 = time.perf_counter()   # set-up is counted from here

import sys  # noqa: E402

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], _T0))
