"""Chip smoke test: drive the repo's three hot paths once on one TPU chip.

    python chip_smoke.py               # phases 1-5 on jax.devices()[0]
    python chip_smoke.py --four-chips  # the placed large_chunked sweep over
                                       # four chips vs the same plan unplaced

Phases (one chip, all pinned to `jax.devices()[0]`):

  1. mc_fig3      — `run_mc` at the paper's Fig. 3 point (MSD-like data,
                    N=500, d=90, Rayleigh, 300 gbma steps, three energies):
                    the OTA slot must resolve to the Pallas kernel and match
                    the inline route; Theorem-1 bound holds, risks finite and
                    falling.
  2. mc_large     — ROADMAP's `large_chunked` workload (N=4096, d=24, 150
                    steps, 1024 seeds) under `auto_plan`, Pallas vs inline on
                    the same plan, plus an 8-seed slice on this process's CPU
                    backend against the same slice on the chip.
  3. mc_nsweep    — N in {100, 200, 400} as one padded call: exactly one
                    engine compile, matching the per-N runs.
  4. serve_mc     — the sweep server's selftest (`repro.launch.serve_mc`).
  5. train_gbma / train_momentum — `repro.launch.train --arch repro-100m` at
                    full width: the fused route and the transport route
                    (Pallas OTA kernel per parameter leaf); loss finite at
                    every step and lower at the last than at the first.

Each phase prints one `phase ...` line: wall seconds, the part of them spent
tracing, lowering and compiling, the number of XLA compiles, the device's
peak bytes in use so far, and the comparison it made. These are smoke
numbers, not benchmark numbers. The last line of stdout is one JSON object,
`{"ok": true, "device": {...}}`, printed only when every phase passed. The
script runs in one process and starts no other: the chip belongs to one
process at a time. Without a TPU it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Workload sizes. The rehearsal on the CPU shrinks these in its own
# process; the script itself runs them as written.
FIG3 = {"n": 500, "dim": 90, "steps": 300, "seeds": 32,
        "eps": (0.5, 1.0, 1.5)}
LARGE = {"n": 4096, "dim": 24, "steps": 150, "seeds": 1024, "slice": 8}
NSWEEP = {"n_grid": (100, 200, 400), "dim": 90, "steps": 100, "seeds": 8}
SERVE = ["--selftest"]
TRAIN = {"arch": "repro-100m", "steps": 6, "batch": 8, "seq": 256,
         "nodes": 8, "reduced": False}

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_COMPILES = {"seconds": 0.0, "count": 0}


class PhaseFailure(RuntimeError):
    pass


def _on_duration(event, duration_secs, **_):
    if event in _COMPILE_EVENTS:
        _COMPILES["seconds"] += duration_secs
        if event == _COMPILE_EVENTS[-1]:
            _COMPILES["count"] += 1


def _require_tpu(devices) -> None:
    if devices[0].platform != "tpu":
        raise PhaseFailure(
            f"no TPU: JAX's first device is {devices[0].platform!r} "
            f"({devices[0].device_kind}); this script only runs on a TPU")


def _rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailure(what)


def _peak_bytes(devices) -> list:
    """Each device's peak bytes in use since the process started."""
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def _phase(name: str, fn, devices) -> None:
    """Run one phase and print its line; a failing phase raises."""
    c0, n0 = _COMPILES["seconds"], _COMPILES["count"]
    t0 = time.perf_counter()
    checks = fn()
    wall = time.perf_counter() - t0
    peaks = _peak_bytes(devices)
    print(f"phase {name}: seconds={wall:.2f} "
          f"compile_seconds={_COMPILES['seconds'] - c0:.2f} "
          f"compiles={_COMPILES['count'] - n0} "
          f"peak_bytes={peaks if len(peaks) > 1 else peaks[0]} "
          f"checks={json.dumps(checks)}", flush=True)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def phase_fig3():
    import numpy as np

    from benchmarks.common import MSDProblem
    from repro.core.channel import ChannelConfig
    from repro.core.mc import resolve_ota_impl, run_mc
    from repro.core.theory import stepsize_theorem1
    from repro.kernels.ota import ops as ota_ops

    n, steps, seeds = FIG3["n"], FIG3["steps"], FIG3["seeds"]
    prob = MSDProblem.make(n, dim=FIG3["dim"])
    chs = [ChannelConfig(fading="rayleigh", scale=1.0, noise_std=1.0,
                         energy=float(n) ** (eps - 2.0))
           for eps in FIG3["eps"]]
    betas = [stepsize_theorem1(prob.pc, ch, n, safety=0.9) for ch in chs]
    args = (prob.to_mc(), chs, "gbma", betas, steps, seeds)
    ota_ops.trace_count(reset=True)
    kern = run_mc(*args, pc=prob.pc, shard_seeds=False, ota_impl="auto")
    route = resolve_ota_impl(kern.plan.ota_impl, (n,))
    _check(route == "pallas" and ota_ops.trace_count() > 0,
           f"fig3: OTA slot resolved to {route!r} "
           f"(kernel traces {ota_ops.trace_count()}), expected the Pallas "
           "kernel")
    inline = run_mc(*args, shard_seeds=False, ota_impl="inline")
    rel = _rel(kern.mean, inline.mean)
    _check(rel <= 1e-5, f"fig3: pallas vs inline mean rel {rel:.3e} > 1e-5")
    _check(bool(np.all(np.isfinite(kern.risks))), "fig3: non-finite risks")
    falling = bool(np.all(kern.mean[:, -1] < kern.mean[:, 0]))
    _check(falling, "fig3: mean risk did not fall")
    holds = bool(np.all(kern.mean <= kern.bounds * 1.05))
    _check(holds, "fig3: Theorem-1 bound violated")
    return {"route": route, "pallas_vs_inline_rel": rel,
            "final_mean": kern.mean[:, -1].tolist(), "bound_holds": holds}


def phase_large(cpu_device):
    import jax
    import numpy as np

    from benchmarks.common import MSDProblem
    from repro.core.channel import ChannelConfig
    from repro.core.mc import auto_plan, run_mc
    from repro.kernels.ota import ops as ota_ops

    n, dim, steps, seeds = (LARGE["n"], LARGE["dim"], LARGE["steps"],
                            LARGE["seeds"])
    prob = MSDProblem.make(n, dim=dim)
    ch = ChannelConfig(fading="rayleigh", scale=1.0, noise_std=1.0,
                       energy=1.0 / n)
    # auto_plan on a one-chip topology: the chip's reported memory limit
    # sizes the chunks, and nothing is placed on other visible chips
    plan = auto_plan(n_rows=1, seeds=seeds, steps=steps, n_max=n, dim=dim,
                     device_count=1)
    _check(not plan.keep_seed_curves and plan.seed_chunk is not None,
           f"large: auto_plan did not chunk the all-live sweep: {plan}")
    args = (prob.to_mc(), [ch], "gbma", [0.01], steps, seeds)
    ota_ops.trace_count(reset=True)
    kern = run_mc(*args, plan=plan)
    _check(ota_ops.trace_count() > 0, "large: the Pallas kernel never ran")
    inline = run_mc(*args, plan=plan.replace(ota_impl="inline"))
    rel = _rel(kern.mean, inline.mean)
    _check(rel <= 1e-5, f"large: pallas vs inline mean rel {rel:.3e}")
    _check(bool(np.all(np.isfinite(kern.mean)))
           and kern.mean[0, -1] < kern.mean[0, 0],
           "large: mean risk not finite or not falling")
    out = {"plan": {"seed_chunk": plan.seed_chunk,
                    "n_shards": plan.n_shards},
           "pallas_vs_inline_rel": rel}

    k = LARGE["slice"]
    slice_plan = plan.replace(seed_chunk=None, keep_seed_curves=True)
    chip = run_mc(prob.to_mc(), [ch], "gbma", [0.01], steps, k,
                  plan=slice_plan)
    if cpu_device is None:
        out["cpu_slice"] = "no cpu backend in this process"
        return out
    with jax.default_device(cpu_device):
        cpu = run_mc(prob.to_mc(), [ch], "gbma", [0.01], steps, k,
                     plan=slice_plan.replace(ota_impl="inline"))
    rel_cpu = _rel(chip.mean, cpu.mean)
    _check(rel_cpu <= 1e-5,
           f"large: {k}-seed chip vs cpu mean rel {rel_cpu:.3e} > 1e-5")
    out["chip_vs_cpu_rel"] = rel_cpu
    return out


def phase_nsweep():
    from benchmarks.common import MSDProblem
    from repro.core.channel import ChannelConfig
    from repro.core.mc import clear_cache, run_mc, trace_count

    grid, steps, seeds = NSWEEP["n_grid"], NSWEEP["steps"], NSWEEP["seeds"]
    mcs = [MSDProblem.make(n, dim=NSWEEP["dim"]).to_mc() for n in grid]
    chs = [ChannelConfig(fading="rayleigh", noise_std=1.0) for _ in grid]
    betas = [0.005] * len(grid)
    clear_cache()
    sweep = run_mc(mcs, chs, "gbma", betas, steps, seeds, shard_seeds=False)
    compiles = trace_count()
    _check(compiles == 1, f"nsweep: {compiles} engine compiles, expected 1")
    rels = []
    for i, mc in enumerate(mcs):
        solo = run_mc(mc, [chs[i]], "gbma", [betas[i]], steps, seeds,
                      shard_seeds=False, ota_impl="inline")
        rels.append(_rel(sweep.risks[i], solo.risks[0]))
    _check(max(rels) <= 1e-5, f"nsweep: sweep vs per-N rel {rels}")
    return {"engine_compiles": compiles, "sweep_vs_per_n_rel": rels}


def phase_serve():
    from repro.launch import serve_mc

    rc = serve_mc.main(SERVE)
    _check(rc == 0, f"serve_mc selftest exit code {rc}")
    return {"selftest_rc": rc}


def phase_train(aggregator: str):
    from repro.launch import train

    argv = ["--arch", TRAIN["arch"], "--steps", str(TRAIN["steps"]),
            "--batch", str(TRAIN["batch"]), "--seq", str(TRAIN["seq"]),
            "--nodes", str(TRAIN["nodes"]), "--aggregator", aggregator]
    if aggregator == "momentum":
        # the receiver momentum is the momentum: plain GD applies it
        # (theta <- theta - beta m, the engine's step rule)
        argv += ["--optimizer", "gd"]
    if TRAIN["reduced"]:
        argv.append("--reduced")
    hist = train.main(argv)
    losses = [h["loss"] for h in hist]
    _check(len(losses) == TRAIN["steps"],
           f"train: {len(losses)} logged steps, expected {TRAIN['steps']}")
    _check(all(math.isfinite(x) for x in losses),
           f"train {aggregator}: non-finite loss {losses}")
    _check(losses[-1] < losses[0],
           f"train {aggregator}: loss did not fall {losses}")
    return {"losses": losses}


def _compiled_text(jitted_name: str, run):
    """Run `run()` while recording the argument shapes of the execution
    layer's `jitted_name` program; return run()'s result and the text of
    that program compiled for the recorded shapes."""
    import jax

    from repro.core.mc import exec as exec_mod

    real = getattr(exec_mod, jitted_name)
    seen = []

    def spy(*args, **kwargs):
        if not seen:
            # shapes only: run_mc's operands are uncommitted, and the
            # program's own mesh places them
            seen.append(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                if isinstance(x, jax.Array) else x, (args, kwargs)))
        return real(*args, **kwargs)

    setattr(exec_mod, jitted_name, spy)
    try:
        out = run()
    finally:
        setattr(exec_mod, jitted_name, real)
    _check(bool(seen), f"{jitted_name} never ran")
    args, kwargs = seen[0]
    return out, real.lower(*args, **kwargs).compile().as_text()


def phase_four_chips(devices):
    """The placed large_chunked sweep over every chip vs the same plan
    unplaced on chip 0; prints the compiled program's collectives."""
    import re

    from benchmarks.common import MSDProblem
    from repro.core.channel import ChannelConfig
    from repro.core.mc import run_mc

    n, dim, steps, seeds = (LARGE["n"], LARGE["dim"], LARGE["steps"],
                            LARGE["seeds"])
    prob = MSDProblem.make(n, dim=dim)
    ch = ChannelConfig(fading="rayleigh", scale=1.0, noise_std=1.0,
                       energy=1.0 / n)
    args = (prob.to_mc(), [ch], "gbma", [0.01], steps, seeds)
    placed, hlo = _compiled_text("_mc_moments_merge",
                                 lambda: run_mc(*args, plan="auto"))
    plan = placed.plan
    _check(plan.n_shards == len(devices),
           f"four-chips: auto plan placed {plan.n_shards} seed shards on "
           f"{len(devices)} chips")
    peaks = _peak_bytes(devices)
    _check(min(peaks) >= 0.25 * max(peaks),
           f"four-chips: placed peaks {peaks} — the work did not spread")
    unplaced = run_mc(*args, plan=plan.replace(n_shards=0, row_shards=1))
    rel = _rel(placed.mean, unplaced.mean)
    _check(rel <= 1e-6, f"four-chips: placed vs unplaced rel {rel:.3e}")
    collectives = {}
    for op in ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all"):
        hits = len(re.findall(rf" {op}(?:-start)?\(", hlo))
        if hits:
            collectives[op] = hits
    _check(bool(collectives), "four-chips: no collective in the placed HLO")
    return {"plan": {"seed_chunk": plan.seed_chunk,
                     "n_shards": plan.n_shards,
                     "row_shards": plan.row_shards},
            "placed_peak_bytes": peaks, "placed_vs_unplaced_rel": rel,
            "collectives": collectives}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the placed sweep over four chips and "
                         "its unplaced comparison")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print("chip_smoke: src/repro not found next to this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(HERE, "src"), HERE]

    import jax
    from jax import monitoring

    try:
        devices = jax.devices()
        _require_tpu(devices)
    except Exception as e:  # no backend, or not a TPU: no result line
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    print(f"chip_smoke: devices={[d.device_kind for d in devices]} "
          f"jax={jax.__version__} cache={enable_compile_cache()}",
          flush=True)
    monitoring.register_event_duration_secs_listener(_on_duration)
    try:
        if args.four_chips:
            _check(len(devices) == 4,
                   f"--four-chips needs 4 chips, found {len(devices)}")
            _phase("four_chips", lambda: phase_four_chips(devices), devices)
        else:
            try:
                cpu = jax.devices("cpu")[0]
            except RuntimeError:
                cpu = None
            dev0 = devices[0]
            with jax.default_device(dev0):
                _phase("mc_fig3", phase_fig3, [dev0])
                _phase("mc_large", lambda: phase_large(cpu), [dev0])
                _phase("mc_nsweep", phase_nsweep, [dev0])
                _phase("serve_mc", phase_serve, [dev0])
                _phase("train_gbma", lambda: phase_train("gbma"), [dev0])
                _phase("train_momentum", lambda: phase_train("momentum"),
                       [dev0])
    except PhaseFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
