"""Execution plans: the explicit plan → place → run → reduce pipeline.

`run_mc` used to expose the execution layer as a bag of hand-set knobs
(`rng_plan`, `seed_chunk`, `keep_seed_curves`, `ota_impl`, `shard_seeds`).
An `ExecPlan` makes the whole execution strategy one explicit, inspectable
record:

  * **plan**   — `auto_plan(...)` derives every field from the analytic
    memory model (`exec.estimate_peak_bytes`), a device-memory budget and
    the visible device topology; or build an `ExecPlan` by hand.
  * **place**  — `n_shards` / `row_shards` lay the seed and sweep-row axes
    out over a real `(rows, mc)` device mesh (`compat.shard_map`). The
    hoisted counter-based RNG plan materializes each trajectory's streams
    *inside* the mapped region, so every device draws exactly the streams
    of the seeds it owns — chunk streams are location-independent by
    construction and curves do not depend on placement.
  * **run**    — the seed-chunked scheduler (`exec.run_chunked`) feeds
    chunks through one compiled program; `run_mc(resume_dir=...)`
    checkpoints the running moments between chunks (`repro.checkpoint`)
    so an interrupted sweep resumes bit-identically.
  * **reduce** — per-chunk two-pass moments merged with Chan's parallel
    algorithm (`exec.chan_merge`), tree-reduced across devices
    (`lax.psum` over the 'mc' axis) into donated accumulators.

The legacy kwargs still work: `run_mc` builds the equivalent plan from
them (behavior-pinned — see `engine.run_mc`). Pass `plan="auto"` to let
`auto_plan` choose, or an `ExecPlan` to pin every field.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Optional

import jax

# The CI-class container the scheduler is sized against (the same figure
# the benchmark's MEM_BUDGET_GIB uses) — the CPU backend's budget, which
# reports no device memory limit.
DEFAULT_MEMORY_BUDGET_BYTES = 2 * 2**30
# Per-device working-set target for chunk sizing: chunks small enough to
# run cache-resident on CPU-class devices (the measured regime of the
# `large_chunked` benchmark entry — ~100 MiB at the hand-tuned chunk=32),
# while staying big enough to amortize per-chunk dispatch.
DEFAULT_CHUNK_TARGET_BYTES = 128 * 2**20


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Capped-exponential-backoff retry budget for one seed chunk (or one
    serving quantum). A chunk that raises — injected fault, OOM, XLA
    error, executor death — is re-attempted up to `max_attempts` times
    total, waiting `delay_s(attempt)` between attempts. Counter-based RNG
    makes the retried chunk replay its exact streams, so a sweep that
    survives k faults within budget is bit-identical to the fault-free
    run (pinned in tests/test_fault_tolerance.py).

    max_attempts: total attempts per chunk (1 = no retry).
    base_delay_s: backoff before the 2nd attempt; doubles per attempt.
    cap_delay_s:  backoff ceiling.
    sleep:        injectable sleep callable (tests/serving pass a virtual
                  clock's sleep; None = `time.sleep`).
    """

    max_attempts: int = 3
    base_delay_s: float = 0.05
    cap_delay_s: float = 2.0
    sleep: Optional[Callable] = None

    def delay_s(self, attempt: int) -> float:
        """Backoff after failed attempt number `attempt` (1-based)."""
        return min(self.cap_delay_s,
                   self.base_delay_s * 2 ** max(attempt - 1, 0))

    def wait(self, attempt: int) -> None:
        (self.sleep if self.sleep is not None else time.sleep)(
            self.delay_s(attempt))


@dataclasses.dataclass(frozen=True)
class ExecPlan:
    """One sweep's complete execution strategy (see module docstring).

    rng_plan:   'hoisted' (counter-based streams materialized outside the
                scan) or 'inscan' (legacy per-slot draw chains).
    seed_chunk: run the seed axis in blocks of this size through one
                compiled program; None = all seeds live in a single call.
                Must divide the seed count.
    n_shards:   seed-axis placement — the 'mc' mesh axis size. None =
                auto (use every visible device when the live seed count
                divides evenly, like the legacy `shard_seeds=None`);
                0 or 1 = single-device; k >= 2 places each chunk's seed
                axis across k devices (k must divide the live seed count).
    row_shards: sweep-row placement — the 'rows' mesh axis size (must
                divide the row count). 1 = rows stay on the seed mesh.
    keep_seed_curves: False reduces per-seed curves to (mean, ci95) on
                device — Chan-merged moments under chunking.
    ota_impl:   'auto' | 'inline' | 'pallas' | 'interpret' | 'ref' routing
                of the OTA slot (`slots.resolve_ota_impl`).
    retry:      a `RetryPolicy` for chunk-level fault isolation in
                `exec.run_chunked` (None = fail fast, the legacy
                behavior). Retried chunks replay their counter-based RNG
                streams, so surviving a fault never perturbs results.
    """

    rng_plan: str = "hoisted"
    seed_chunk: Optional[int] = None
    n_shards: Optional[int] = None
    row_shards: int = 1
    keep_seed_curves: bool = True
    ota_impl: str = "auto"
    retry: Optional[RetryPolicy] = None

    def replace(self, **kw) -> "ExecPlan":
        """A copy with the given fields swapped (frozen dataclass)."""
        return dataclasses.replace(self, **kw)

    def asdict(self) -> dict:
        """Plain-dict view (benchmark/topology records). The retry
        policy's injectable sleep callable is not JSON material — it is
        recorded by qualname (or None)."""
        d = dataclasses.asdict(self)
        if d.get("retry") is not None and d["retry"].get("sleep") is not None:
            sleep = d["retry"]["sleep"]
            d["retry"]["sleep"] = getattr(sleep, "__qualname__", repr(sleep))
        return d


def validate_plan(plan: ExecPlan, *, seeds: int, n_rows: int) -> None:
    """Shape-level plan validation against one call's (seeds, rows)."""
    if plan.rng_plan not in ("hoisted", "inscan"):
        raise ValueError(
            f"rng_plan must be 'hoisted' or 'inscan', got {plan.rng_plan!r}")
    if plan.seed_chunk is not None:
        if plan.seed_chunk <= 0:
            raise ValueError(
                f"seed_chunk must be positive, got {plan.seed_chunk}")
        if seeds % plan.seed_chunk != 0:
            raise ValueError(
                f"seeds ({seeds}) must divide into seed_chunk "
                f"({plan.seed_chunk}) blocks — pad the seed count or pick "
                "a chunk that divides it")
    s_live = plan.seed_chunk if plan.seed_chunk is not None else seeds
    if plan.n_shards is not None and plan.n_shards > 1 \
            and s_live % plan.n_shards != 0:
        raise ValueError(
            f"n_shards={plan.n_shards} must divide the live seed count "
            f"({s_live} = seed_chunk or seeds)")
    if plan.row_shards < 1 or n_rows % plan.row_shards != 0:
        raise ValueError(
            f"row_shards={plan.row_shards} must be >= 1 and divide the "
            f"row count ({n_rows})")
    if plan.retry is not None:
        if plan.retry.max_attempts < 1:
            raise ValueError(
                f"retry.max_attempts must be >= 1, "
                f"got {plan.retry.max_attempts}")
        if plan.retry.base_delay_s < 0 or plan.retry.cap_delay_s < 0:
            raise ValueError(
                "retry delays must be nonnegative, got "
                f"base_delay_s={plan.retry.base_delay_s}, "
                f"cap_delay_s={plan.retry.cap_delay_s}")


def resolve_seed_shards(plan: ExecPlan, seeds: int,
                        device_count: Optional[int] = None) -> int:
    """The concrete 'mc' mesh size for this call: 0 = no seed placement.

    `n_shards=None` keeps the legacy auto rule (`shard_seeds=None`): every
    visible device when the live seed count divides evenly, else off.
    """
    s_live = plan.seed_chunk if plan.seed_chunk is not None else seeds
    ndev = jax.device_count() if device_count is None else device_count
    if plan.n_shards is None:
        return ndev if (ndev > 1 and s_live % ndev == 0) else 0
    n_sh = int(plan.n_shards)
    n_sh = 0 if n_sh <= 1 else n_sh
    if n_sh * plan.row_shards > ndev:
        raise ValueError(
            f"plan places {n_sh or 1} x {plan.row_shards} shards but only "
            f"{ndev} device(s) are visible — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=K to force "
            "host devices, or shrink the plan")
    return n_sh


def device_memory_budget_bytes() -> int:
    """Per-device memory budget: the backend-reported limit
    (`memory_stats()['bytes_limit']`, at 80% headroom). The CPU backend
    reports none and gets the CI-class default the scheduler is sized
    against; an accelerator that does not report its limit is an error —
    a budget guessed for a CPU container would mis-size every chunk."""
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return DEFAULT_MEMORY_BUDGET_BYTES
    stats = dev.memory_stats()
    if not stats or not stats.get("bytes_limit"):
        raise RuntimeError(
            f"{dev.device_kind} ({dev.platform}) reports no bytes_limit in "
            "memory_stats(); pass memory_budget_bytes= explicitly")
    return int(0.8 * stats["bytes_limit"])


def _divisors_desc(n: int) -> list:
    ds = set()
    for i in range(1, int(math.isqrt(n)) + 1):
        if n % i == 0:
            ds.add(i)
            ds.add(n // i)
    return sorted(ds, reverse=True)


def auto_plan(*, n_rows: int, seeds: int, steps: int, n_max: int, dim: int,
              algo_set=("gbma",), n_antennas=None, m_sizes=(),
              b_max: int = 0, invert_channel: bool = False,
              participation_on: bool = False,
              keep_seed_curves: Optional[bool] = None,
              rng_plan: str = "hoisted", ota_impl: str = "auto",
              memory_budget_bytes: Optional[int] = None,
              target_chunk_bytes: Optional[int] = None,
              device_count: Optional[int] = None,
              cost_model: str = "analytic",
              calibration_path: Optional[str] = None,
              _model=None) -> ExecPlan:
    """Derive an `ExecPlan` from the workload, the analytic memory model
    and the device topology. Fully deterministic given its inputs: every
    returned field is concrete (no `None` placement), so the plan is a
    complete record of how the sweep will execute.

    Placement: the seed axis takes `gcd(seeds, device_count)` shards, the
    row axis the largest divisor of `n_rows` that fits the remaining
    devices — the full mesh is used whenever the axes divide.

    Chunking: the sweep chunks when the all-live per-device estimate
    (`exec.estimate_peak_bytes`) exceeds `target_chunk_bytes` (default
    128 MiB — the cache-resident regime the `large_chunked` benchmark
    measures); the chunk is the largest divisor of `seeds` (a multiple of
    the seed shards) whose per-device estimate fits the target, bounded
    by `memory_budget_bytes` in any case.

    `keep_seed_curves=None` resolves to False exactly when the plan
    chunks (the throughput configuration — only (C, steps+1) statistics
    transfer); pass True explicitly when per-seed curves are needed
    (`energy_to_target`).

    `cost_model="measured"` re-prices the seed-chunk choice with the
    calibration-fed cost model (`repro.core.mc.costmodel`): every
    shardable chunk that fits the memory budget is a candidate, ranked
    by `CostModel.predict_run_us` (compute at the measured slot rate ×
    the working-set profile factor, plus per-call dispatch). The choice
    is conservative: it deviates from the analytic chunk only when the
    predicted win exceeds 5% — microbench fits are not trusted for
    coin-flip margins. When no calibration artifact matches this
    platform/device count (`costmodel.load_cost_model` → None) the
    analytic path runs EXACTLY — behavior-pinned in
    `tests/test_costmodel.py`. `_model` injects a `CostModel` directly
    (tests); `calibration_path` overrides the artifact location.
    """
    from repro.core.mc.exec import estimate_peak_bytes

    if cost_model not in ("analytic", "measured"):
        raise ValueError(
            f"cost_model must be 'analytic' or 'measured', "
            f"got {cost_model!r}")

    ndev = jax.device_count() if device_count is None else int(device_count)
    budget = device_memory_budget_bytes() if memory_budget_bytes is None \
        else int(memory_budget_bytes)
    target = DEFAULT_CHUNK_TARGET_BYTES if target_chunk_bytes is None \
        else int(target_chunk_bytes)
    target = min(target, budget)

    n_sh = math.gcd(seeds, max(ndev, 1))
    row_sh = math.gcd(n_rows, max(ndev // max(n_sh, 1), 1))

    def per_device(chunk: Optional[int]) -> int:
        est = estimate_peak_bytes(
            n_rows=n_rows, seeds=seeds, steps=steps, n_max=n_max, dim=dim,
            algo_set=tuple(algo_set), seed_chunk=chunk,
            n_antennas=n_antennas, m_sizes=tuple(m_sizes), b_max=b_max,
            keep_seed_curves=False, rng_plan=rng_plan,
            invert_channel=invert_channel,
            participation_on=participation_on,
            n_shards=max(n_sh, 1), row_shards=max(row_sh, 1))
        return est["per_device_peak_bytes"]

    seed_chunk: Optional[int] = None
    if per_device(None) > target:
        fits_target = [c for c in _divisors_desc(seeds)
                       if c % max(n_sh, 1) == 0 and per_device(c) <= target]
        if fits_target:
            seed_chunk = fits_target[0]
        else:
            # nothing meets the cache target: fall back to the smallest
            # shardable chunk that at least fits the hard budget (or the
            # smallest chunk outright — best effort, never an error)
            candidates = [c for c in reversed(_divisors_desc(seeds))
                          if c % max(n_sh, 1) == 0]
            fits_budget = [c for c in candidates if per_device(c) <= budget]
            seed_chunk = (max(fits_budget) if fits_budget
                          else candidates[0])
        if seed_chunk >= seeds:
            seed_chunk = None  # chunking the full axis is the all-live call

    if cost_model == "measured":
        model = _model
        if model is None:
            from repro.core.mc import costmodel as _costmodel
            model = _costmodel.load_cost_model(calibration_path,
                                               device_count=ndev)
        if model is not None:
            from repro.core.mc.costmodel import Workload

            wl = Workload(n_rows=n_rows, seeds=seeds, steps=steps,
                          n_max=n_max, dim=dim, algo_set=tuple(algo_set),
                          m_sizes=tuple(m_sizes), b_max=b_max)

            def candidate(chunk: Optional[int]) -> ExecPlan:
                return ExecPlan(
                    rng_plan=rng_plan, seed_chunk=chunk,
                    n_shards=0 if n_sh <= 1 else n_sh,
                    row_shards=max(row_sh, 1),
                    keep_seed_curves=False, ota_impl=ota_impl)

            chunks = [None if c >= seeds else c
                      for c in _divisors_desc(seeds)
                      if c % max(n_sh, 1) == 0]
            fits = [c for c in chunks if per_device(c) <= budget]
            if fits:
                pred = {c: model.predict_run_us(candidate(c), wl,
                                                device_count=ndev)
                        for c in fits}
                best = min(fits, key=lambda c: (pred[c],
                                                -(c or seeds)))
                # conservative: keep the analytic chunk inside a 5%
                # prediction band — deviate only for a clear win
                if seed_chunk in pred \
                        and pred[seed_chunk] <= 1.05 * pred[best]:
                    best = seed_chunk
                seed_chunk = best

    if keep_seed_curves is None:
        keep_seed_curves = seed_chunk is None
    return ExecPlan(
        rng_plan=rng_plan, seed_chunk=seed_chunk,
        n_shards=0 if n_sh <= 1 else n_sh, row_shards=max(row_sh, 1),
        keep_seed_curves=bool(keep_seed_curves), ota_impl=ota_impl)
