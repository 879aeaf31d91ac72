"""Batched, jitted Monte Carlo engine: row assembly + public entry point.

The paper's figures reproduce the expectation in Eq. (14) by averaging
excess-risk curves over seeds; the engine runs a whole sweep as one
compiled call:

    shard_map(seeds over 'mc' devices) ∘ vmap(rows) ∘ vmap(seeds) ∘ scan(steps)

with the excess-risk curve computed **on-device inside the scan**. A batch
row is a (problem, channel params, algo, stepsize) tuple; problems come
from the `PROBLEMS` registry (`mc/problems.py`), per-slot algorithm updates
from the `ALGO_REGISTRY` (`mc/slots.py`), and every RNG draw from the
reference-twin samplers (`mc/sampling.py`). HOW a call executes — the
hoisted counter-based RNG plan, the seed-chunked scheduler with donated
carries, the on-device seed reduction — lives in the execution layer
(`mc/exec.py`, knobs `rng_plan` / `seed_chunk` / `keep_seed_curves`, see
docs/performance.md). `repro.core.montecarlo` is the back-compat façade
re-exporting this package's public surface.

Stochastic problems (a registered `stochastic_grad_row`, e.g. `logistic`)
draw per-slot minibatch indices from a dedicated data-key stream
(`fold_in(trajectory key, _DATA_STREAM)` — disjoint from the slot keys, so
channel/noise draws are unchanged by the minibatching). The minibatch size
is the `run_mc(batch_frac=...)` knob — scalar or per-row, so a
batch-fraction sweep is ONE compile; `batch_frac=1.0` (the default)
statically disables sampling and is bit-identical to running the same
problem registered without a stochastic gradient.

`run_mc(ota_impl=)` routes the single-antenna OTA superposition through
`repro.kernels.ota.ota_edge_aggregate` ('pallas' on TPU, 'interpret' in
the Pallas interpreter, 'ref' jnp oracle) or the inline einsum
('inline'); 'auto' picks pallas on TPU when eligible and the inline
einsum otherwise (`slots.resolve_ota_impl`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import ChannelConfig
from repro.core.mc import exec as exec_mod
from repro.core.mc.exec import (  # noqa: F401  (re-exported surface)
    _DATA_STREAM,
    _mc_core,
    clear_cache,
    trace_count,
)
from repro.core.mc.plan import (
    ExecPlan,
    auto_plan,
    resolve_seed_shards,
    validate_plan,
)
from repro.core.mc.problems import MCProblem, MCProblemBatch, PROBLEMS
from repro.core.mc.slots import ALGO_REGISTRY, resolve_ota_impl
from repro.core.theory import ProblemConstants, theorem1_bound

Array = jax.Array


# --------------------------------------------------------------------------
# batched channel parameters
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ChannelBatch:
    """Stack of C `ChannelConfig`s sharing one fading family.

    The family string is static (it selects the gain-sampling code path);
    everything else is a (C,) f32 array and vmaps in a single compile.
    """

    fading: str
    params: dict  # {'scale','noise_std','energy','phase_error_max','rician_k'}
    configs: tuple  # the original ChannelConfigs (host side, for bounds)

    @classmethod
    def stack(cls, cfgs: Sequence[ChannelConfig]) -> "ChannelBatch":
        fams = {c.fading for c in cfgs}
        if len(fams) != 1:
            raise ValueError(
                f"one ChannelBatch = one fading family, got {sorted(fams)}; "
                "issue one run_mc call per family")
        arr = lambda name: jnp.asarray(
            [getattr(c, name) for c in cfgs], jnp.float32)
        return cls(
            fading=cfgs[0].fading,
            params={
                "scale": arr("scale"),
                "noise_std": arr("noise_std"),
                "energy": arr("energy"),
                "phase_error_max": arr("phase_error_max"),
                "rician_k": arr("rician_k"),
            },
            configs=tuple(cfgs),
        )

    def __len__(self) -> int:
        return len(self.configs)


# --------------------------------------------------------------------------
# results
# --------------------------------------------------------------------------
@dataclasses.dataclass
class MCResult:
    """Host-side result of one engine call.

    risks:      (C, S, steps+1) per-row per-seed excess-risk curves, or
                None under `keep_seed_curves=False` (the curves were
                seed-reduced on device and never transferred).
    mean:       (C, steps+1) seed average (the Eq. 14 expectation estimate).
    ci95:       (C, steps+1) 1.96 * standard error over seeds (0 if S == 1).
    cum_energy: (C, S, steps) cumulative transmitted energy Σ E_N ||x_k||²
                of the actually-transmitted vectors — x_k = g_k for every
                algorithm except `blind_ec`, whose power budget truncates
                x_k = α(g_k + e_k). None under `keep_seed_curves=False`.
    bounds:     (C, steps+1) Theorem-1 bound per row (None unless problem
                constants were supplied AND every row is single-antenna
                'gbma' — the setting Theorem 1 covers).
    """

    risks: Optional[np.ndarray]
    mean: np.ndarray
    ci95: np.ndarray
    cum_energy: Optional[np.ndarray]
    bounds: Optional[np.ndarray]
    plan: Optional[ExecPlan] = None  # the resolved ExecPlan this ran under


def _resolve_n_shards(n_seeds: int, shard_seeds: Optional[bool]) -> int:
    """0 = plain path; k > 0 = shard_map over a ('mc',) mesh of k devices."""
    if shard_seeds is False:
        return 0
    ndev = jax.device_count()
    if shard_seeds is None:
        return ndev if (ndev > 1 and n_seeds % ndev == 0) else 0
    if n_seeds % ndev != 0:
        raise ValueError(
            f"shard_seeds=True needs seeds ({n_seeds}) divisible by the "
            f"device count ({ndev})")
    return ndev


def _resolve_batch_frac(batch_frac, n_rows: int, batch_prob, problem):
    """-> (spec, b_max, b_counts) for the stochastic path, or
    (None, 0, None) for the static full-batch path."""
    if isinstance(batch_frac, (int, float, np.integer, np.floating)):
        fracs = (float(batch_frac),) * n_rows
    else:
        fracs = tuple(float(f) for f in batch_frac)
        if len(fracs) != n_rows:
            raise ValueError(f"need one batch_frac per row: "
                             f"{len(fracs)} vs C={n_rows}")
    if any(not (0.0 < f <= 1.0) for f in fracs):
        raise ValueError(f"batch_frac must be in (0, 1], got {fracs}")
    if all(f == 1.0 for f in fracs):
        return None, 0, None  # exact full-batch gradients, no sampling
    stochastic = batch_prob.stochastic if batch_prob is not None \
        else getattr(problem, "stochastic", False)
    kind = batch_prob.kind if batch_prob is not None \
        else getattr(problem, "kind", "")
    spec = PROBLEMS.get(kind)
    if not stochastic or spec is None or spec.stochastic_grad_row is None:
        raise ValueError(
            f"batch_frac={fracs} needs a stochastic problem kind (a "
            "registered stochastic_grad_row); "
            f"got kind={kind!r}")
    data = batch_prob.data if batch_prob is not None else problem.data
    k = data[spec.sample_axis_field].shape[-2]
    b_counts = tuple(max(1, int(round(f * k))) for f in fracs)
    return spec, max(b_counts), b_counts


# --------------------------------------------------------------------------
# public entry point
# --------------------------------------------------------------------------
def run_mc(
    problem: Union[MCProblem, MCProblemBatch, Sequence[MCProblem]],
    channels: Sequence[ChannelConfig] | ChannelBatch,
    algo: str | Sequence[str],
    betas: Sequence[float] | np.ndarray,
    steps: int,
    seeds: int,
    *,
    theta0: Optional[np.ndarray] = None,
    seed0: int = 0,
    n_antennas: Optional[Union[int, Sequence[int]]] = None,
    invert_channel: bool = False,
    h_min: float = 0.3,
    pc: Optional[Union[ProblemConstants,
                       Sequence[ProblemConstants]]] = None,
    momentum: float = 0.9,
    power_budget: Optional[Union[float, Sequence[float]]] = None,
    shard_seeds: Optional[bool] = None,
    batch_frac: Union[float, Sequence[float]] = 1.0,
    ota_impl: Optional[str] = None,
    rng_plan: Optional[str] = None,
    seed_chunk: Optional[int] = None,
    keep_seed_curves: Optional[bool] = None,
    plan: Union[ExecPlan, str, None] = None,
    resume_dir: Optional[str] = None,
    memory_budget_bytes: Optional[int] = None,
    participation: Union[float, Sequence[float]] = 1.0,
) -> MCResult:
    """Run `seeds` Monte Carlo trajectories for each batch row.

    A row is a (problem, channel, algo, stepsize) tuple; `problem` and
    `algo` broadcast when a single one is given. Passing a sequence of
    problems (node counts may differ — they are padded to N_max) or a
    sequence of algos runs the whole sweep in ONE engine compile.

    Seed s uses `jax.random.key(seed0 + s)` — the same stream the sequential
    reference path (`benchmarks.common.average_runs`) consumes, so results
    are directly comparable. With `pc` supplied (one `ProblemConstants` or
    one per row) the Theorem-1 bound rides along — only when every row is
    single-antenna 'gbma', the setting Theorem 1 covers; mixed-algo calls
    get `bounds=None`.

    `n_antennas`: the edge antenna count M. An int broadcasts (static;
    OTA algos take the MRC path, blind algos combine over M). A sequence
    gives one M per row AS DATA — the antenna axis pads to max(M) and an
    M-sweep batches into the same single compile (each row's key split
    replays `split(key, m)` for its true m). Required for blind/blind_ec.

    `power_budget`: per-slot, per-node transmit budget in squared-norm
    units of the transmitted vector (scalar or one per row; default
    unbounded). Only `blind_ec` rows enforce it, carrying the truncated
    remainder in their local residual.

    `shard_seeds` shards the seed axis over devices on a 'mc' mesh axis
    (None: auto when divisible; no-op on one device).

    `batch_frac` (scalar or one per row): fraction of each node's local
    samples drawn per slot for stochastic problem kinds (`logistic`). 1.0
    (default) computes the exact full-batch gradient with no sampling —
    bit-identical to a deterministic registration of the same problem;
    fractions < 1 draw with-replacement minibatches per slot, and a
    per-row fraction sweep is one compile.

    `ota_impl`: 'auto' (inline einsum; pallas kernel on TPU when the node
    count is static), 'inline' forces the einsum, 'pallas' (TPU only),
    'interpret' (the kernel in the Pallas interpreter) or 'ref' force the
    `repro.kernels.ota.ota_edge_aggregate` path for the single-antenna OTA
    superposition.

    Execution strategy (docs/performance.md): HOW the sweep executes is
    one `repro.core.mc.plan.ExecPlan`. Three ways to choose it:

    `plan=` an `ExecPlan` pins every field (rng_plan, seed_chunk,
    n_shards, row_shards, keep_seed_curves, ota_impl); `plan="auto"`
    derives one from the analytic memory model, the per-device memory
    budget (`memory_budget_bytes=`, default: the accelerator's reported
    limit, or 2 GiB on the CPU) and the visible device topology via `auto_plan`; or leave
    `plan=None` and set the legacy knobs below — they build the
    equivalent plan (behavior-pinned), and mixing them with `plan=` is
    an error. The resolved plan is recorded on `MCResult.plan`.

    `rng_plan`: 'hoisted' (default) materializes every randomness stream
    in one batched counter-based draw per stream outside the scan —
    stream-identical to the per-slot split chains, leaving the scan body
    pure linear algebra; 'inscan' keeps the legacy in-scan draws (the
    benchmark baseline).

    `seed_chunk`: run the seed axis in blocks of this size through one
    compiled program, bounding peak device memory to
    O(C · seed_chunk · steps · n_max); must divide `seeds`. None (default)
    runs all seeds in one call.

    `keep_seed_curves`: False reduces the per-seed curves to (mean, ci95)
    on device — only (C, steps+1) statistics transfer to host, and
    `MCResult.risks`/`cum_energy` are None (so `energy_to_target`, which
    needs per-seed curves, requires the default True).

    `resume_dir`: chunked reduced sweeps (`seed_chunk` set,
    `keep_seed_curves=False`) checkpoint their (chunk cursor, Chan
    moments) to this directory after every chunk and restore from it on
    the next call — an interrupted-then-resumed sweep is bit-identical
    to an uninterrupted one (counter-based RNG; see
    `exec.run_chunked`).

    `participation` (scalar or one per row): per-slot node participation
    probability p ∈ (0, 1] — each step each node independently transmits
    with probability p and stays silent (zero transmission, zero energy)
    otherwise, drawn from one extra hoisted counter-based stream
    (disjoint fold_in constant, so enabling dropout shifts no other
    draw). The edge still normalizes by the full N — the paper-level
    graceful-degradation setting (ROADMAP item b, arXiv 2310.03371) —
    and a per-row p sweep is ONE compile (p is data). The default 1.0
    statically disables the stream and is bit-identical to a run without
    the knob.
    """
    ch_batch = channels if isinstance(channels, ChannelBatch) \
        else ChannelBatch.stack(list(channels))
    n_rows = len(ch_batch)
    betas = jnp.asarray(betas, jnp.float32)
    if betas.shape != (n_rows,):
        raise ValueError(f"need one stepsize per row: "
                         f"{betas.shape} vs C={n_rows}")
    algos = (algo,) * n_rows if isinstance(algo, str) else tuple(algo)
    if len(algos) != n_rows:
        raise ValueError(f"need one algo per row: {len(algos)} vs C={n_rows}")
    for a in algos:
        if a not in ALGO_REGISTRY:
            raise ValueError(f"unknown algo {a!r}; expected one of "
                             f"{tuple(ALGO_REGISTRY)}")
    specs = [ALGO_REGISTRY[a] for a in algos]
    if rng_plan is not None and rng_plan not in ("hoisted", "inscan"):
        raise ValueError(
            f"rng_plan must be 'hoisted' or 'inscan', got {rng_plan!r}")
    if plan is not None:
        clash = [name for name, v in (
            ("rng_plan", rng_plan), ("seed_chunk", seed_chunk),
            ("keep_seed_curves", keep_seed_curves),
            ("ota_impl", ota_impl), ("shard_seeds", shard_seeds))
            if v is not None]
        if clash:
            raise ValueError(
                f"plan= already pins the execution strategy; drop the "
                f"conflicting legacy knob(s) {clash} or encode them in "
                "the ExecPlan")
        if isinstance(plan, str) and plan != "auto":
            raise ValueError(
                f"plan must be an ExecPlan or the string 'auto', "
                f"got {plan!r}")
    if memory_budget_bytes is not None and plan != "auto":
        raise ValueError(
            "memory_budget_bytes only parameterizes plan='auto' — an "
            "explicit ExecPlan or the legacy knobs already fix the chunk "
            "size")

    # ---- normalize the antenna axis ------------------------------------
    if n_antennas is None or isinstance(n_antennas, (int, np.integer)):
        if n_antennas is not None:
            n_antennas = int(n_antennas)
        m_per_row, m_sizes = None, ()
    else:
        m_per_row = tuple(int(m) for m in n_antennas)
        if len(m_per_row) != n_rows:
            raise ValueError(f"need one antenna count per row: "
                             f"{len(m_per_row)} vs C={n_rows}")
        if any(m < 1 for m in m_per_row):
            raise ValueError(f"antenna counts must be >= 1: {m_per_row}")
        m_sizes = tuple(sorted(set(m_per_row)))
        n_antennas = None  # the static broadcast arg is off in per-row mode
    if any(s.blind for s in specs) and n_antennas is None and not m_sizes:
        raise ValueError(
            "blind/blind_ec need n_antennas (the edge antenna count M)")

    # ---- normalize the problem axis ------------------------------------
    if isinstance(problem, MCProblemBatch):
        batch_prob = problem
    elif isinstance(problem, MCProblem):
        batch_prob = None  # closure path: one problem shared by all rows
    else:
        probs = list(problem)
        if len(probs) == 1:
            batch_prob = None
            problem = probs[0]
        else:
            if len(probs) != n_rows:
                raise ValueError(
                    f"need one problem per row: {len(probs)} vs C={n_rows}")
            batch_prob = MCProblemBatch.stack(probs)

    # stochastic minibatching needs the row-based data path; lift a single
    # broadcast problem into a C-row batch (cheap: data is small)
    sto_spec, b_max, b_counts = _resolve_batch_frac(
        batch_frac, n_rows, batch_prob, problem)
    sgrad_fn = sto_spec.stochastic_grad_row if sto_spec is not None else None
    if sgrad_fn is not None and batch_prob is None:
        batch_prob = MCProblemBatch.stack([problem] * n_rows)

    if batch_prob is not None:
        row_based = True
        grad_fn, risk_fn = batch_prob.grad_fn, batch_prob.risk_fn
        data = dict(batch_prob.data)
        n_nodes = batch_prob.n_nodes
        dim, n_max = batch_prob.dim, batch_prob.n_max
    else:
        row_based = False
        grad_fn, risk_fn = problem.grad_fn, problem.risk_fn
        n_nodes = (problem.n_nodes,) * n_rows
        dim, n_max = problem.dim, problem.n_nodes
        data = {"mask": jnp.ones((n_rows, n_max), jnp.float32)}

    n_sizes = tuple(sorted(set(n_nodes)))
    algo_set = tuple(dict.fromkeys(algos))

    # ---- normalize node participation ----------------------------------
    if isinstance(participation, (int, float, np.integer, np.floating)):
        parts = (float(participation),) * n_rows
    else:
        parts = tuple(float(p) for p in participation)
        if len(parts) != n_rows:
            raise ValueError(f"need one participation per row: "
                             f"{len(parts)} vs C={n_rows}")
    if any(not (0.0 < p <= 1.0) for p in parts):
        raise ValueError(f"participation must be in (0, 1], got {parts}")
    # static on/off only — the probabilities themselves are data, so a
    # per-row p sweep shares one compile; p = 1.0 everywhere disables the
    # mask stream entirely (bit-identical to a run without the knob, and
    # params stays key-identical so resume fingerprints don't shift)
    participation_on = any(p < 1.0 for p in parts)

    # ---- resolve the execution plan ------------------------------------
    # Three sources, one record: an explicit ExecPlan, "auto" (derived
    # from the memory model + topology), or the legacy kwargs building
    # the equivalent plan. The legacy shim is behavior-pinned: every
    # sentinel (None) maps to the exact pre-plan default, and
    # shard_seeds=True resolves through the legacy rule (including its
    # divisibility error) before the plan is built.
    if isinstance(plan, ExecPlan):
        eff_plan = plan
    elif plan == "auto":
        eff_plan = auto_plan(
            n_rows=n_rows, seeds=seeds, steps=steps, n_max=n_max, dim=dim,
            algo_set=algo_set, n_antennas=n_antennas, m_sizes=m_sizes,
            b_max=b_max, invert_channel=invert_channel,
            participation_on=participation_on,
            memory_budget_bytes=memory_budget_bytes)
    else:
        shim_shards: Optional[int] = None
        if shard_seeds is False:
            shim_shards = 0
        elif shard_seeds is True:
            shim_shards = _resolve_n_shards(
                seed_chunk if seed_chunk is not None else seeds, True)
        eff_plan = ExecPlan(
            rng_plan="hoisted" if rng_plan is None else rng_plan,
            seed_chunk=seed_chunk,
            n_shards=shim_shards,
            row_shards=1,
            keep_seed_curves=(True if keep_seed_curves is None
                              else keep_seed_curves),
            ota_impl="auto" if ota_impl is None else ota_impl)
    validate_plan(eff_plan, seeds=seeds, n_rows=n_rows)
    n_shards = resolve_seed_shards(eff_plan, seeds)
    if resume_dir is not None and (eff_plan.seed_chunk is None
                                   or eff_plan.keep_seed_curves):
        raise ValueError(
            "resume_dir requires a chunked reduced sweep — a plan with "
            "seed_chunk set and keep_seed_curves=False (only the chunk "
            "cursor and moment accumulators are checkpointed)")

    ota_resolved = resolve_ota_impl(eff_plan.ota_impl, n_sizes)
    # static promise for the hoisted plan's phase-stream shortcut: every
    # row's phase draw is over [-0, 0] (cos(0)=1, value-identical to
    # skip). Only hoist-eligible calls (hoisted plan, one algorithm WITH
    # a hoist twin) set it — elsewhere nothing reads it, and a static
    # True/False split would needlessly fragment the jit cache across
    # phase settings that the legacy body treats as pure data.
    phase_zero = (
        eff_plan.rng_plan == "hoisted" and len(algo_set) == 1
        and ALGO_REGISTRY[algo_set[0]].hoist_draws is not None
        and all(float(c.phase_error_max) == 0.0
                for c in ch_batch.configs))
    params = dict(ch_batch.params)
    params["n_nodes"] = jnp.asarray(n_nodes, jnp.float32)
    params["algo_idx"] = jnp.asarray(
        [algo_set.index(a) for a in algos], jnp.int32)
    params["gamma"] = jnp.asarray(
        [momentum if s.uses_gamma else 0.0 for s in specs], jnp.float32)
    params["nest"] = jnp.asarray(
        [1.0 if s.nesterov else 0.0 for s in specs], jnp.float32)
    params["ec"] = jnp.asarray(
        [1.0 if s.error_feedback else 0.0 for s in specs], jnp.float32)
    if power_budget is None:
        budgets = (float("inf"),) * n_rows
    elif isinstance(power_budget, (int, float, np.integer, np.floating)):
        budgets = (float(power_budget),) * n_rows
    else:
        budgets = tuple(float(b) for b in power_budget)
        if len(budgets) != n_rows:
            raise ValueError(f"need one power budget per row: "
                             f"{len(budgets)} vs C={n_rows}")
    params["tx_budget"] = jnp.asarray(budgets, jnp.float32)
    if m_sizes:
        params["n_antennas"] = jnp.asarray(m_per_row, jnp.float32)
    if b_counts is not None:
        # int32, NOT float32: a lane count is integral and must survive
        # exactly (float32 rounds above 2^24); the single consumer divides
        # by it after an explicit float cast
        params["b_count"] = jnp.asarray(b_counts, jnp.int32)
    if participation_on:
        params["participation"] = jnp.asarray(parts, jnp.float32)

    t0 = jnp.zeros((dim,), jnp.float32) if theta0 is None \
        else jnp.asarray(theta0, jnp.float32)
    seed_ints = np.arange(seed0, seed0 + seeds, dtype=np.int32)
    core_kwargs = dict(
        grad_fn=grad_fn, risk_fn=risk_fn, row_based=row_based,
        algo_set=algo_set, fading=ch_batch.fading, steps=steps,
        n_sizes=n_sizes, n_antennas=n_antennas, m_sizes=m_sizes,
        invert_channel=invert_channel, h_min=h_min,
        sgrad_fn=sgrad_fn, b_max=b_max, ota_impl=ota_resolved,
        rng_plan=eff_plan.rng_plan, phase_zero=phase_zero,
        sample_idx_fn=(sto_spec.sample_indices_row
                       if sto_spec is not None else None),
        sgrad_idx_fn=(sto_spec.stochastic_grad_from_idx
                      if sto_spec is not None else None),
        participation_on=participation_on)
    if eff_plan.seed_chunk is not None:
        risks, cum_e, mean, ci95 = exec_mod.run_chunked(
            params, betas, t0, seed_ints, data,
            seed_chunk=eff_plan.seed_chunk,
            keep_seed_curves=eff_plan.keep_seed_curves,
            n_shards=n_shards, row_shards=eff_plan.row_shards,
            core_kwargs=core_kwargs, resume_dir=resume_dir,
            retry=eff_plan.retry)
    else:
        seed_arr = jnp.asarray(seed_ints)
        if eff_plan.keep_seed_curves:
            risks, cum_e = _mc_core(params, betas, t0, seed_arr, data,
                                    n_shards=n_shards,
                                    row_shards=eff_plan.row_shards,
                                    **core_kwargs)
            risks, cum_e = np.asarray(risks), np.asarray(cum_e)
            mean, ci95 = exec_mod.host_seed_stats(risks)
        else:
            mean, ci95 = exec_mod._mc_stats(
                params, betas, t0, seed_arr, data, n_shards=n_shards,
                row_shards=eff_plan.row_shards, **core_kwargs)
            mean, ci95 = np.asarray(mean), np.asarray(ci95)
            risks = cum_e = None
    bounds = None
    if pc is not None:
        pcs = [pc] * n_rows if isinstance(pc, ProblemConstants) else list(pc)
        if len(pcs) != n_rows:
            raise ValueError(f"need one ProblemConstants per row: "
                             f"{len(pcs)} vs C={n_rows}")
        if all(s.theorem1 for s in specs) and n_antennas is None \
                and not m_sizes:
            ks = np.arange(1, steps + 2)
            bounds = np.stack([
                theorem1_bound(ks, float(b), row_pc, cfg, n)
                for b, cfg, row_pc, n in zip(
                    np.asarray(betas), ch_batch.configs, pcs, n_nodes)])
    return MCResult(
        risks=risks, mean=mean.astype(np.float32),
        ci95=ci95.astype(np.float32), cum_energy=cum_e,
        bounds=bounds, plan=eff_plan)


def slice_result(res: MCResult, rows: Union[slice, Sequence[int]]) -> MCResult:
    """A per-row view of an `MCResult`: the given row slice (or index
    sequence) of every (C, ...) array, `None` leaves passed through.

    The row axis is the sweep axis — a coalesced batch (several callers'
    sweeps packed into one engine call, `repro.serving.mc_server`) demuxes
    back into per-caller results with one `slice_result` per caller. The
    sliced arrays are numpy views of the batch result, and `plan` (a
    whole-call property) rides along unchanged.
    """
    idx = rows if isinstance(rows, slice) else list(rows)
    pick = lambda a: None if a is None else a[idx]
    return MCResult(risks=pick(res.risks), mean=pick(res.mean),
                    ci95=pick(res.ci95), cum_energy=pick(res.cum_energy),
                    bounds=pick(res.bounds), plan=res.plan)


def energy_to_target(res: MCResult, target: float) -> np.ndarray:
    """Per-row mean (over seeds) total transmitted energy until the risk
    curve first hits `target` (paper Fig. 6).

    risks[k] is the risk of θ_k, reached after k transmission slots, and
    cum_energy[j] is the energy of slots 1..j+1 — so a first hit at index
    k costs cum_energy[k-1], and a target already met at initialization
    (k == 0) costs nothing. Seeds that never hit spend the full-horizon
    energy.
    """
    if res.risks is None or res.cum_energy is None:
        raise ValueError(
            "energy_to_target needs per-seed curves — run with the default "
            "keep_seed_curves=True")
    c, s, kp1 = res.risks.shape
    hit_mask = res.risks <= target
    hit = np.argmax(hit_mask, axis=2)  # first True, 0 when none
    hit = np.where(hit_mask.any(axis=2), hit, kp1 - 1)
    # prepend the zero-cost column so index k charges cum_energy[k-1]
    ce = np.concatenate(
        [np.zeros((c, s, 1), res.cum_energy.dtype), res.cum_energy], axis=2)
    per_seed = np.take_along_axis(ce, hit[:, :, None], axis=2)[..., 0]
    return per_seed.mean(axis=1)
