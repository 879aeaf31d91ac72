"""The benchmark harness: one cell, one run, driven by data.

Everything that belongs to a single configuration, traffic mix or
per-layer metric is a file of its own, found by the name that
`BENCHMARK.json` gives it:

  configs/<config>.json          sizes, `source`, `reduced`, `assumed`, and
                                 the name of its plain reference
  reference/<reference>.py       that plain reference
  traffic/<config>.<traffic>.json  the traffic mix: the generator that
                                 makes it, and its parameters
  generators/<generator>.py      the general generator of one kind of
                                 traffic (sweeps, training steps)
  metrics/<metric>.py            one reader per per-layer metric: `read(ctx)`
                                 returns a number, or None when it finds
                                 nothing to read
  peaks.json                     the chip's published peaks by device_kind

A later cell or metric adds files and entries and edits none of these.

A run: set-up (imports, the chip, data and weights from the seed, the
compile cache, the warm call) is timed as `setup_s`; the window then runs
the cell's closed loop for `--seconds` and no longer starts a call after
it; nothing may compile inside it. With `--trace 1` the window is traced
and the per-layer metrics are read from the trace. After the window the
peak device memory is read, the program's state is freed and the
generator compares what the window produced with the plain reference.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CACHE_DIRNAME = ".chipbench_cache"
_COMPILE_EVENT_PREFIX = "/jax/core/compile/"


class NoChip(RuntimeError):
    """The machine lacks what the cell asks for: no result is printed."""


class RunFailure(RuntimeError):
    """The run broke a rule of the harness (e.g. a compile in the window)."""


# --------------------------------------------------------------------------
# finding things by name
# --------------------------------------------------------------------------
def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Catalog:
    """Looks up configurations, traffic mixes, generators, references and
    metric readers by name, first in `extra_dirs` and then in this
    directory."""

    def __init__(self, root: str, spec: dict, extra_dirs=()):
        self.root = root
        self.spec = spec
        self.dirs = [*extra_dirs, BENCH_DIR]

    def path(self, sub: str, fname: str) -> str:
        for d in self.dirs:
            p = os.path.join(d, sub, fname)
            if os.path.isfile(p):
                return p
        raise FileNotFoundError(f"no {sub}/{fname} under {self.dirs}")

    def module(self, sub: str, name: str):
        path = self.path(sub, name + ".py")
        mod_name = "chipbench_" + sub + "_" + name.replace(".", "_")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _read_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, config: str, traffic: str) -> dict:
        return _read_json(self.path("traffic", f"{config}.{traffic}.json"))

    def cell(self, workload: str) -> dict:
        cells = [w for w in self.spec["workloads"] if w["name"] == workload]
        if not cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        w = cells[0]
        e2e = [m for m in self.spec["end_to_end"]
               if "workloads" not in m or workload in m["workloads"]]
        e2e_names = {m["name"] for m in e2e}
        per_layer = [m for m in self.spec["per_layer"]
                     if (workload in m["workloads"] if "workloads" in m
                         else m["moves"] in e2e_names)]
        return {"workload": w, "config": self.config(w["config"]),
                "traffic": self.traffic(w["config"], w["traffic"]),
                "end_to_end": e2e, "per_layer": per_layer}


def load_peaks(path: str | None = None) -> dict:
    return _read_json(path or os.path.join(BENCH_DIR, "peaks.json"))["kinds"]


def peak_for(kind: str, peaks: dict) -> dict:
    """The published peaks of `kind`; an unknown chip is an error."""
    if kind not in peaks:
        raise NoChip(f"device_kind {kind!r} has no entry in peaks.json "
                     f"(known: {sorted(peaks)})")
    return peaks[kind]


# --------------------------------------------------------------------------
# compile events
# --------------------------------------------------------------------------
class CompileLog:
    """Sums JAX's trace, lower and backend-compile durations (the
    listener of chip_smoke.py) and counts the events, so that set-up's
    compile seconds and any compile inside the window show."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0

    def __call__(self, event, duration_secs, **_):
        if event.startswith(_COMPILE_EVENT_PREFIX):
            self.seconds += duration_secs
            self.events += 1


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout:
    only a cell's first run there compiles."""
    import jax

    path = os.path.join(root, CACHE_DIRNAME)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int, peaks: dict) -> list:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    peak_for(devices[0].device_kind, peaks)
    return devices[:chips]


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def _window(work, seconds: float, compile_log: CompileLog):
    """The closed loop: the next call starts when the last one's result is
    in hand; no call starts once `seconds` have passed. Returns
    (calls, elapsed seconds from the window's start to the end of its last
    call)."""
    import jax

    events0 = compile_log.events
    n = 0
    with jax.profiler.TraceAnnotation("window"):
        t_start = time.perf_counter()
        deadline = t_start + seconds
        while True:
            work.call(n)
            n += 1
            t_end = time.perf_counter()
            if t_end >= deadline:
                break
    if compile_log.events != events0:
        raise RunFailure(
            f"{compile_log.events - events0} compile event(s) inside the "
            "measured window: a shape was not warmed up in set-up")
    return n, t_end - t_start


def run_cell(*, catalog: Catalog, workload: str, seed: int, seconds: float,
             trace: bool, t0: float, devices: list, peak: dict) -> dict:
    """Set up, measure and check one cell on `devices`; returns the result
    object (without printing)."""
    from jax import monitoring

    compile_log = CompileLog()
    monitoring.register_event_duration_secs_listener(compile_log)
    try:
        return _run_cell(catalog, workload, seed, seconds, trace, t0,
                         devices, peak, compile_log)
    finally:
        from jax._src import monitoring as _monitoring

        _monitoring.unregister_event_duration_listener(compile_log)


def _finite(x):
    """A number as JSON can hold it: NaN and infinities become null."""
    x = float(x)
    return x if math.isfinite(x) else None


def _run_cell(catalog, workload, seed, seconds, trace, t0, devices, peak,
              compile_log) -> dict:
    import jax

    from chipbench import trace as trace_mod

    cell = catalog.cell(workload)
    kind = catalog.module("generators", cell["traffic"]["generator"])
    work = kind.Workload(cell["config"], cell["traffic"], seed,
                              devices, catalog)
    work.setup()
    setup_s = time.perf_counter() - t0
    compile_s = compile_log.seconds

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(trace_dir)
    try:
        n_calls, elapsed = _window(work, seconds, compile_log)
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak_bytes = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in devices)
    record = {"setup_s": setup_s, "compile_s": compile_s, "calls": n_calls,
              "window_s": elapsed, "devices": devices,
              "peak": peak, **work.counts(n_calls, elapsed)}

    metrics, breakdown, device_extra = {}, None, {}
    if trace:
        summary = trace_mod.summarize(
            trace_mod.load_trace(trace_dir), window_span="window",
            span_names=work.span_names, n_devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device_extra = {"busy_s": summary.busy_s,
                        "window_s": summary.window_s}
        breakdown = summary.breakdown()
        ctx = {"trace": summary, "run": record}
        for m in cell["per_layer"]:
            value = catalog.module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": _finite(value),
                                      "unit": m["unit"]}
    else:
        e2e = work.end_to_end(n_calls, elapsed)
        e2e["setup_s"] = setup_s
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": _finite(e2e[m["name"]]),
                                  "unit": m["unit"]}

    work.release()
    checks, failed = work.check()
    correct = bool(checks) and all(c["value"] <= c["limit"] for c in checks)
    result = {
        "correct": correct,
        "attempted": n_calls,
        "failed": int(failed),
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": peak_bytes, **device_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": _finite(c["value"]),
                                    "limit": c["limit"]} for c in checks}
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] is not None and c["value"] <= c["limit"] \
            else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv, *, t0: float, root: str) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chipbench: the system under test ({src}/repro) is not in "
              "this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    try:
        spec = _read_json(os.path.join(root, "BENCHMARK.json"))
        catalog = Catalog(root, spec)
        chips = catalog.cell(args.workload)["workload"]["chips"]
        peaks = load_peaks()
        enable_compile_cache(root)
        devices = require_chips(chips, peaks)
        result = run_cell(catalog=catalog, workload=args.workload,
                          seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t0=t0, devices=devices,
                          peak=peak_for(devices[0].device_kind, peaks))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    except Exception:  # noqa: BLE001 - a run that breaks prints no result
        traceback.print_exc()
        return 1
    print_result(result)
    return 0
