"""MC sweep-server launcher.

`python -m repro.launch.serve_mc` runs a demo traffic mix through the
coalescing server (`repro.serving.mc_server`) and prints the router's
batching stats; `--selftest` additionally pins the two serving
invariants on a mixed compatible/incompatible request set and exits
nonzero on violation — the CI `serve-smoke` job runs this mode:

  * K signature-compatible concurrent requests execute as ONE `_mc_core`
    compile — `trace_count()` equals the number of distinct signatures;
  * every demuxed per-request result matches a dedicated solo `run_mc`
    call to <= 1e-6 relative.

`--selftest --chaos` additionally drives the fault-tolerance paths (the
CI `chaos-smoke` job runs this): one injected engine-layer chunk fault
retried bit-identically, one transient quantum failure recovered under
`McServeConfig.retry`, and one mid-run deadline expiry resolving with a
`PartialResult` that matches a dedicated run over the completed seeds —
all on a virtual clock, no wall-clock sleeps.

`main(argv)` takes its arguments as a list and returns the exit code, so
a caller in the same process (`chip_smoke.py`) runs the selftest as a
function.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import sys
import time
from typing import Optional, Sequence

import numpy as np

from repro.core.channel import ChannelConfig
from repro.core.mc import (
    MCProblemBatch,
    clear_cache,
    quadratic_mc_problem,
    run_mc,
    trace_count,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.mc_server import McServeConfig, SweepRequest, serve_sync


def _problem(n: int, dim: int, seed: int):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    y = rng.normal(size=(n,)).astype(np.float32)
    return quadratic_mc_problem(x, y, 0.1, np.zeros(dim, np.float32))


def _demo_requests(steps: int, seeds: int) -> list:
    """A mixed set: three coalescible quadratic/gbma sweeps differing
    only in row data (N, noise, stepsize), plus one momentum request and
    one longer-horizon request — three distinct signatures."""
    mk = lambda n, noise, beta, seed: SweepRequest(
        problem=_problem(n, 8, seed),
        channels=[ChannelConfig(fading="rayleigh", noise_std=noise)],
        algo="gbma", betas=[beta], steps=steps, seeds=seeds)
    reqs = [mk(12, 0.5, 0.08, 0), mk(20, 1.0, 0.05, 1), mk(16, 0.1, 0.1, 2)]
    reqs.append(SweepRequest(
        problem=_problem(16, 8, 3),
        channels=[ChannelConfig(fading="rayleigh")],
        algo="momentum", betas=[0.05], steps=steps, seeds=seeds))
    reqs.append(SweepRequest(
        problem=_problem(12, 8, 4),
        channels=[ChannelConfig(fading="rayleigh")],
        algo="gbma", betas=[0.08], steps=steps + 10, seeds=seeds))
    return reqs


def _solo(req: SweepRequest):
    """The dedicated-call reference: the same row-based engine path the
    server uses, one request per call."""
    return run_mc(MCProblemBatch.stack([req.problem]),
                  req.channels, req.algo, req.betas,
                  req.steps, req.seeds, seed0=req.seed0,
                  batch_frac=req.batch_frac, n_antennas=req.n_antennas,
                  power_budget=req.power_budget, momentum=req.momentum,
                  theta0=req.theta0, shard_seeds=False)


def _selftest(steps: int, seeds: int, quantum: int,
              bucket_base: float = 2.0) -> int:
    # The demo mix spans two N-buckets inside the gbma signature, but a
    # fresh server has seen neither shape class — first sight merges
    # (compiles dominate the cost model's prediction), so the bucketed
    # router keeps the one-compile-per-signature invariant this test pins.
    reqs = _demo_requests(steps, seeds)
    n_sigs = 3
    clear_cache()
    results = serve_sync(reqs, McServeConfig(quantum_seeds=quantum,
                                             bucket_base=bucket_base))
    compiles = trace_count()
    stats = serve_sync.last_stats
    ok = True
    if compiles != n_sigs:
        ok = False
        print(f"FAIL: {compiles} compiles for {n_sigs} distinct "
              f"signatures ({len(reqs)} requests)")
    for i, (req, res) in enumerate(zip(reqs, results)):
        solo = _solo(req)
        rel = np.max(np.abs(res.risks - solo.risks)
                     / np.maximum(np.abs(solo.risks), 1e-12))
        if not (rel <= 1e-6):
            ok = False
            print(f"FAIL: request {i} demux mismatch, rel={rel:.3e}")
    n_batches = len(stats.batches)
    if n_batches != n_sigs:
        ok = False
        print(f"FAIL: {n_batches} batches for {n_sigs} signatures")
    if any(b["pad_flops_ratio"] < 1.0 for b in stats.batches):
        ok = False
        print("FAIL: pad_flops_ratio < 1.0 (padded FLOPs below useful)")
    verdict = "PASS" if ok else "FAIL"
    print(f"selftest {verdict}: {len(reqs)} requests -> {n_batches} "
          f"batches, {compiles} compiles, batches="
          f"{[(b['requests'], b['rows'], b['quanta']) for b in stats.batches]}, "
          f"pad_ratios="
          f"{[b['pad_flops_ratio'] for b in stats.batches]}, "
          f"occupancy={stats.bucket_occupancy}")
    return 0 if ok else 1


class _VirtualClock:
    """Injected server clock: advanced only by scripted events."""

    def __init__(self):
        self.now = 0.0

    def time(self) -> float:
        return self.now

    async def sleep(self, dt: float) -> None:
        self.now += dt
        await asyncio.sleep(0)


def _chaos(steps: int, seeds: int, quantum: int) -> int:
    """Chaos scenarios for `--selftest --chaos`: scripted faults at the
    engine and serving layers, each checked against its fault-free
    reference. Returns 0/1 like `_selftest`."""
    from repro.core.mc import ExecPlan, RetryPolicy
    from repro.core.mc import exec as exec_mod
    from repro.serving.mc_server import (
        InlineExecutor,
        McSweepServer,
        PartialResult,
    )

    ok = True

    def rel(a, b):
        return np.max(np.abs(np.asarray(a) - np.asarray(b))
                      / np.maximum(np.abs(np.asarray(b)), 1e-12))

    # -- scenario 0: engine-layer chunk retry is bit-identical ----------
    args = (_problem(12, 8, 0),
            [ChannelConfig(fading="rayleigh", noise_std=0.5)],
            "gbma", [0.08], steps, seeds)
    plan = ExecPlan(seed_chunk=quantum, keep_seed_curves=False)
    clean = run_mc(*args, plan=plan)
    fired = []

    def fail_first_attempts(info):
        if info["attempt"] == 1:  # every chunk fails once
            fired.append(info["off"])
            raise RuntimeError("chaos: injected chunk fault")

    remove = exec_mod.install_chunk_fault_hook(fail_first_attempts)
    try:
        survived = run_mc(*args, plan=plan.replace(
            retry=RetryPolicy(max_attempts=2, sleep=lambda dt: None)))
    finally:
        remove()
    if not (fired and np.array_equal(survived.mean, clean.mean)
            and np.array_equal(survived.ci95, clean.ci95)):
        ok = False
        print(f"FAIL: chunk retry not bit-identical after {len(fired)} "
              f"injected faults")

    class _ChaosExecutor(InlineExecutor):
        """Fails the `fail_at`-th engine call once; jumps the virtual
        clock by `jump` after the `jump_after`-th call (a scripted slow
        quantum)."""

        def __init__(self, clock, fail_at=None, jump_after=None,
                     jump=0.0):
            self.clock = clock
            self.fail_at = fail_at
            self.jump_after = jump_after
            self.jump = jump
            self.n = 0

        async def run(self, fn, info=None):
            idx, self.n = self.n, self.n + 1
            if idx == self.fail_at:
                self.fail_at = None
                raise RuntimeError("chaos: transient quantum failure")
            out = await super().run(fn, info)
            if idx == self.jump_after:
                self.clock.now += self.jump
            return out

    async def drive(srv, reqs):
        tasks = [asyncio.ensure_future(srv.submit(r)) for r in reqs]
        await asyncio.sleep(0)
        await srv.drain()
        return await asyncio.gather(*tasks, return_exceptions=True)

    # -- scenario 1: transient quantum failure recovered by cfg.retry ---
    req = _demo_requests(steps, seeds)[0]
    clock = _VirtualClock()
    srv = McSweepServer(
        McServeConfig(quantum_seeds=quantum,
                      retry=RetryPolicy(max_attempts=3,
                                        base_delay_s=0.01)),
        executor=_ChaosExecutor(clock, fail_at=0), clock=clock)
    (res,) = asyncio.run(drive(srv, [req]))
    retries = srv.stats.retries
    if isinstance(res, Exception) or retries < 1 \
            or rel(res.risks, _solo(req).risks) > 1e-6:
        ok = False
        print(f"FAIL: retried quantum did not recover to the solo "
              f"result ({res!r}, retries={retries})")

    # -- scenario 2: mid-run deadline expiry -> PartialResult -----------
    reqs = _demo_requests(steps, seeds)[:2]
    hurried = dataclasses.replace(reqs[0], deadline_s=5.0)
    patient = reqs[1]
    clock = _VirtualClock()
    srv = McSweepServer(
        McServeConfig(quantum_seeds=quantum),
        executor=_ChaosExecutor(clock, jump_after=0, jump=10.0),
        clock=clock)
    part, full = asyncio.run(drive(srv, [hurried, patient]))
    part_ref = dataclasses.replace(hurried, seeds=quantum,
                                   deadline_s=None)
    if not (isinstance(part, PartialResult)
            and part.seeds_completed == quantum
            and part.result is not None
            and rel(part.result.risks, _solo(part_ref).risks) <= 1e-6):
        ok = False
        print(f"FAIL: deadline expiry did not degrade gracefully "
              f"({part!r})")
    if isinstance(full, Exception) \
            or rel(full.risks, _solo(patient).risks) > 1e-6:
        ok = False
        print("FAIL: the expired request disturbed its batchmate")
    if srv.stats.deadline_expired != 1:
        ok = False
        print(f"FAIL: deadline_expired={srv.stats.deadline_expired}")

    verdict = "PASS" if ok else "FAIL"
    print(f"chaos {verdict}: {len(fired)} chunk faults retried "
          f"bit-identically, 1 quantum failure recovered "
          f"(retries={retries}), 1 deadline expiry -> "
          f"PartialResult({quantum}/{seeds} seeds)")
    return 0 if ok else 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    """Parse `argv` (None: the command line) and run; returns the exit
    code (nonzero when a selftest check failed)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--quantum", type=int, default=4,
                    help="seeds per scheduling quantum")
    ap.add_argument("--bucket-base", type=float, default=2.0,
                    help="geometric N-bucket base of the pad-waste-aware "
                         "coalescer; <= 1 disables bucketing")
    ap.add_argument("--selftest", action="store_true",
                    help="assert one compile per distinct signature and "
                         "demux == solo run_mc; exit nonzero on failure")
    ap.add_argument("--chaos", action="store_true",
                    help="with --selftest: also run the scripted fault "
                         "scenarios (chunk retry, quantum retry, "
                         "deadline expiry)")
    args = ap.parse_args(argv)
    if args.selftest:
        rc = _selftest(args.steps, args.seeds, args.quantum,
                       args.bucket_base)
        if args.chaos:
            rc |= _chaos(args.steps, args.seeds, args.quantum)
        return rc
    reqs = _demo_requests(args.steps, args.seeds)
    clear_cache()
    t0 = time.time()
    results = serve_sync(reqs, McServeConfig(quantum_seeds=args.quantum,
                                             bucket_base=args.bucket_base))
    dt = time.time() - t0
    stats = serve_sync.last_stats
    print(f"{len(reqs)} requests -> {len(stats.batches)} coalesced "
          f"batches, {trace_count()} compiles, {dt:.1f}s, "
          f"bucket occupancy {stats.bucket_occupancy}")
    for b in stats.batches:
        print(f"  sig={b['signature']} requests={b['requests']} "
              f"rows={b['rows']} seeds={b['seeds']} quanta={b['quanta']} "
              f"n_max={b['n_max']} pad_flops_ratio={b['pad_flops_ratio']}")
    for i, res in enumerate(results):
        print(f"  request {i}: final mean risk {res.mean[:, -1]}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
