"""Pluggable Monte Carlo engine for OTA gradient-descent experiments.

Layers (see each module's docstring):

  * `problems` — problem containers + the open `PROBLEMS` registry
    (`register_problem`); built-ins `quadratic`, `localization`, and the
    stochastic-capable `logistic`.
  * `sampling` — reference-twin RNG samplers (padded sweeps draw at
    N_max / M_max and mask: threefry draws are prefix-stable).
  * `slots`    — per-slot algorithm updates behind `register_algo`
    (`ALGOS` derives from the registry) + each algorithm's `hoist_draws`
    RNG-plan twin.
  * `plan`     — `ExecPlan` (one sweep's execution strategy) +
    `auto_plan` deriving it from the analytic memory model, a memory
    budget and the device topology (or, with `cost_model="measured"`,
    from the calibration-fed cost model).
  * `costmodel` — the measured per-workload cost model: a one-time
    calibration suite persisted as a versioned JSON artifact keyed by
    platform/device-count, `CostModel.predict_step_us/predict_run_us`
    consumed by `auto_plan` and the sweep server's pad-waste-aware
    coalescer, and the cached machine-peaks microbench the roofline
    renders.
  * `exec`     — the execution layer: the compiled `_mc_core` placed on
    a ("rows", "mc") device mesh, the hoisted counter-based RNG plan,
    the seed-chunked resumable scheduler with donated Chan-merged
    moment carries, the on-device seed reduction, and the analytic
    memory model (`estimate_peak_bytes`) — see docs/performance.md.
  * `engine`   — row assembly + the public `run_mc`, `MCResult`,
    `ChannelBatch`, `energy_to_target`.

`repro.core.montecarlo` remains the back-compat import path.
"""
from repro.core.mc.engine import (
    ChannelBatch,
    MCResult,
    clear_cache,
    energy_to_target,
    run_mc,
    slice_result,
    trace_count,
)
from repro.core.mc.costmodel import (
    CalibrationConfig,
    CostModel,
    Workload,
    analytic_cost_model,
    load_cost_model,
)
from repro.core.mc.exec import cache_epoch, estimate_peak_bytes, \
    static_signature
from repro.core.mc.plan import ExecPlan, RetryPolicy, auto_plan, \
    validate_plan
from repro.core.mc.problems import (
    MCProblem,
    MCProblemBatch,
    PROBLEMS,
    ProblemSpec,
    localization_mc_problem,
    logistic_mc_problem,
    quadratic_mc_problem,
    register_problem,
)
from repro.core.mc.slots import (
    ALGO_REGISTRY,
    AlgoSpec,
    SlotCtx,
    register_algo,
    resolve_ota_impl,
)


def __getattr__(name: str):
    if name in ("ALGOS", "_OTA_ALGOS", "_BLIND_ALGOS"):
        from repro.core.mc import slots

        return getattr(slots, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "ALGO_REGISTRY",
    "ALGOS",
    "AlgoSpec",
    "CalibrationConfig",
    "ChannelBatch",
    "CostModel",
    "ExecPlan",
    "Workload",
    "analytic_cost_model",
    "cache_epoch",
    "load_cost_model",
    "MCProblem",
    "MCProblemBatch",
    "MCResult",
    "PROBLEMS",
    "ProblemSpec",
    "RetryPolicy",
    "SlotCtx",
    "auto_plan",
    "clear_cache",
    "energy_to_target",
    "estimate_peak_bytes",
    "localization_mc_problem",
    "logistic_mc_problem",
    "quadratic_mc_problem",
    "register_algo",
    "register_problem",
    "resolve_ota_impl",
    "run_mc",
    "slice_result",
    "static_signature",
    "trace_count",
    "validate_plan",
]
