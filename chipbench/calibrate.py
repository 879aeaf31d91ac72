"""Readings that the limits of `correct` are set from, on the chip, at a
cell's own size: for each seed, the program's gaps to the plain
reference after a short window, the control's (the reference one
precision step lower, put in the program's place) and each fault's.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--calls 2] [--out readings.jsonl]

One process serves every seed, so the set-up is paid once. The
benchmark's own runs never run this; `chipbench/tests` keeps the same
comparisons at a size a test run can hold.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated --seed values")
    ap.add_argument("--calls", type=int, default=2,
                    help="window calls before the readings")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    catalog = harness.Catalog(ROOT, spec)
    cell = catalog.cell(args.workload)
    harness.enable_compile_cache(ROOT)
    devices = harness.require_chips(cell["workload"]["chips"],
                                    harness.load_peaks())
    seeds = [int(s) for s in args.seeds.split(",")]
    kind = catalog.module("generators", cell["traffic"]["generator"])
    work = kind.Workload(cell["config"], cell["traffic"], seeds[0], devices,
                         catalog)
    work.setup()
    print(f"calibrate: set-up {time.perf_counter() - _T0:.1f} s",
          file=sys.stderr, flush=True)
    out = open(args.out, "a") if args.out else None
    for seed in seeds:
        work.reseed(seed)
        work.start()
        t = time.perf_counter()
        for i in range(args.calls):
            work.call(i)
        line = {"workload": args.workload, "seed": seed,
                "call_s": (time.perf_counter() - t) / args.calls,
                **work.calibrate()}
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
