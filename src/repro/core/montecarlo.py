"""Back-compat façade over the `repro.core.mc` package.

The Monte Carlo engine used to live here as a single module; it is now a
package split along its natural layers:

  * `repro.core.mc.problems` — `MCProblem` / `MCProblemBatch`, the open
    `PROBLEMS` registry (`register_problem`) and the library constructors
    (`quadratic_mc_problem`, `localization_mc_problem`,
    `logistic_mc_problem`).
  * `repro.core.mc.sampling` — the reference-twin RNG samplers (padded
    sweeps draw at N_max / M_max and mask).
  * `repro.core.mc.slots`    — per-slot algorithm updates behind
    `register_algo` (`ALGOS` is derived from the registry) + the
    `hoist_draws` RNG-plan twins.
  * `repro.core.mc.exec`     — the execution layer: the compiled
    `_mc_core`, hoisted RNG plan, seed-chunked scheduler, on-device seed
    reduction, memory model (docs/performance.md).
  * `repro.core.mc.engine`   — row assembly + `run_mc`, `MCResult`,
    `ChannelBatch`, `energy_to_target`, the compile counter.

Every name importable from `repro.core.montecarlo` before the split —
public API and the underscore helpers exercised by tests and notebooks —
still resolves here (guarded by `tests/test_backcompat.py`); new code
should import from `repro.core.mc` directly.
"""
from __future__ import annotations

from repro.core.mc import exec as _exec
from repro.core.mc import plan as _plan
from repro.core.mc import problems as _problems
from repro.core.mc import sampling as _sampling
from repro.core.mc import slots as _slots
from repro.core.mc.engine import (
    Array,
    ChannelBatch,
    MCResult,
    _mc_core,
    _resolve_n_shards,
    clear_cache,
    energy_to_target,
    run_mc,
    trace_count,
)
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
    _slot_update,
    register_algo,
)

_SUBMODULES = (_slots, _sampling, _problems, _exec, _plan)


def __getattr__(name: str):
    # registry-derived views must stay live (late register_* calls show up)
    if name in ("ALGOS", "_OTA_ALGOS", "_BLIND_ALGOS"):
        return getattr(_slots, name)
    if name == "_PER_NODE_FIELDS":
        return _problems._per_node_fields()
    if name == "_ROW_FNS":
        return _problems._row_fns()
    # underscore helpers (samplers, row fns, ...) kept importable from the
    # old module path without enumerating them one by one
    for mod in _SUBMODULES:
        if hasattr(mod, name):
            return getattr(mod, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
