"""Run one benchmark cell once on the chip(s) of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are named in
`BENCHMARK.json` at the root of the checkout; everything that belongs to
one of them is a file of its own under `chipbench/` (see
`chipbench/harness.py`). The last line of standard output is one JSON
result; the numbers compared for `correct` close standard error. Without
a TPU, or on a chip whose `device_kind` has no entry in
`chipbench/peaks.json`, the run exits non-zero and prints no result.
"""
import time

_T0 = time.perf_counter()  # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=_T0, root=ROOT))
