"""Trace one cell's window and reduce it to the program's own spans and
scopes, on the chip:

    python3 chipbench/program_profile.py --workload <cell> --seed <n> \
        --seconds <s>

The cell is set up as `chipbench/run.py` sets it up; one window of
`--seconds` runs under the profiler. The last line of standard output is
one JSON object: the window's end-to-end numbers as measured under the
profiler, `trace.summarize`'s busy time and breakdown, the program's
breakdown (`program_trace.summarize`: `program_idle`, `device_scopes`,
`span_stats`, `span_self_s`, `scope_ops`) and the readings of `program_trace.READINGS` that find
something to read. Nothing is compared with a reference: this is a
profile, not a benchmark run.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, program_trace, trace  # noqa: E402


def profile(workload: str, seed: int, seconds: float) -> dict:
    import jax
    from jax import monitoring
    from jax.profiler import ProfileData

    spec = harness._read_json(os.path.join(ROOT, "BENCHMARK.json"))
    catalog = harness.Catalog(ROOT, spec)
    cell = catalog.cell(workload)
    peaks = harness.load_peaks()
    harness.enable_compile_cache(ROOT)
    devices = harness.require_chips(cell["workload"]["chips"], peaks)
    peak = harness.peak_for(devices[0].device_kind, peaks)
    compile_log = harness.CompileLog()
    monitoring.register_event_duration_secs_listener(compile_log)
    kind = catalog.module("generators", cell["traffic"]["generator"])
    work = kind.Workload(cell["config"], cell["traffic"], seed, devices,
                         catalog)
    work.setup()
    setup_s = time.perf_counter() - _T0
    trace_dir = tempfile.mkdtemp(prefix="chipbench_profile_")
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            n_calls, elapsed = harness._window(work, seconds, compile_log)
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        with open(path, "rb") as f:
            raw = f.read()
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    pd = ProfileData.from_serialized_xspace(raw)
    summary = trace.summarize(pd, window_span="window",
                              span_names=work.span_names,
                              n_devices=len(devices))
    program = program_trace.summarize(pd, raw, window_span="window",
                                      n_devices=len(devices))
    run = {"devices": devices, "peak": peak,
           **work.counts(n_calls, elapsed)}
    readings = {}
    for name, fn in program_trace.READINGS.items():
        value = fn(program, summary, run)
        if value is not None:
            readings[name] = value
    return {
        "workload": workload, "seed": seed, "setup_s": setup_s,
        "calls": n_calls, "traced_end_to_end": work.end_to_end(
            n_calls, elapsed),
        "device": {"kind": devices[0].device_kind, "count": len(devices),
                   "busy_s": summary.busy_s,
                   "window_s": summary.window_s},
        "readings": readings,
        "breakdown": {**summary.breakdown(), **program.breakdown()},
        "span_stats": program.span_stats,
        "span_self_s": program.span_self_s,
        "scope_ops": program.scope_ops,
    }


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/program_profile.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        out = profile(args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
