"""Throughput-first execution layer for the Monte Carlo engine.

This module owns HOW a sweep executes; `engine.py` owns WHAT a sweep is
(row assembly, validation, results). Three orthogonal knobs, all surfaced
through `run_mc`:

* **RNG plan** (`rng_plan="hoisted"` default / `"inscan"`): the hoisted
  plan materializes every randomness stream — channel gains, edge noise,
  per-antenna complex fades, fdm per-node noise, minibatch indices — in
  one batched counter-based (threefry) draw per stream OUTSIDE the
  `lax.scan`, as scan inputs, instead of tracing the key-split chains into
  the scan body. The draws replay the per-slot `split` chains key-for-key
  (each algorithm registers a `hoist_draws` twin of its slot fn's draw
  code in `mc/slots.py`), so trajectories are stream-identical to the
  legacy in-scan plan; the scan body is left with pure linear algebra.
  The plan also knows one static shortcut: when every row's
  `phase_error_max` is 0 the precoded-phase draw is skipped entirely
  (cos(0) == 1 exactly, and the phase stream has its own key half, so
  skipping it cannot shift any other draw). `"inscan"` keeps the
  pre-exec-layer engine byte-for-byte — it is the benchmark baseline and
  the fallback for third-party algos registered without a `hoist_draws`.

* **Seed chunking** (`seed_chunk=`): a host-side scheduler runs the seed
  axis in blocks of `seed_chunk`, re-materializing the hoisted draws per
  chunk, so peak device memory is O(C · chunk · steps · n_max) instead of
  O(C · seeds · steps · n_max). One compile covers every chunk (the seed
  ints are data). With `keep_seed_curves=False` the running curve
  statistics are carried between chunks in donated device buffers
  (`jax.jit(..., donate_argnums=...)` — XLA reuses the accumulator
  allocation in place).

* **On-device reduction** (`keep_seed_curves=False`): when the caller
  only needs the seed-mean and ci95 (most figures), the (C, S, steps+1)
  per-seed curves never leave the device — only (C, steps+1) statistics
  transfer to host. Chunked sweeps carry exact per-chunk two-pass moments
  and merge them with Chan's parallel algorithm (`chan_merge`) in donated
  device buffers; under placement the per-shard moments tree-reduce
  across the 'mc' mesh axis (`lax.psum`) before they ever leave the
  mapped region. `energy_to_target` needs per-seed curves and raises if
  they were reduced away.

* **Placement** (`n_shards` / `row_shards`, via `plan.ExecPlan`): the
  live seed axis and the sweep-row axis lay out over a real 2-D
  `("rows", "mc")` device mesh (`compat.shard_map`). The hoisted
  counter-based RNG plan materializes each trajectory's streams inside
  the mapped region — a device draws exactly the streams of the seeds it
  owns, so chunk streams are location-independent by construction and
  curves do not depend on placement.

* **Resume** (`run_chunked(..., resume_dir=)`): the chunked moments path
  persists (chunk cursor, running Chan moments) through
  `repro.checkpoint.ckpt` after every chunk, keyed by a workload
  fingerprint. Counter-based RNG makes an interrupted-then-resumed sweep
  bit-identical to an uninterrupted one.

`estimate_peak_bytes` is the analytic memory model behind the knobs
(documented in docs/performance.md); `benchmarks/bench_montecarlo.py`
records it next to warm/cold timings. `plan.auto_plan` derives a full
`ExecPlan` from it plus the device topology.
"""
from __future__ import annotations

import functools
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.checkpoint import ckpt
from repro.core.mc.slots import ALGO_REGISTRY, SlotCtx

Array = jax.Array

# fold_in constant deriving the per-trajectory minibatch key stream from
# the trajectory key — disjoint from the `split(key, steps)` slot keys
_DATA_STREAM = 0x64617461  # b"data"
# fold_in constant for the per-step node-participation mask stream —
# disjoint from both the slot keys and the minibatch stream, so enabling
# dropout cannot shift any other draw
_PART_STREAM = 0x70617274  # b"part"

_TRACES = obs.Counter()  # engine traces (== XLA compiles)
_CACHE_EPOCH = 0


def cache_epoch() -> int:
    """Monotone counter bumped by every `clear_cache()`. Consumers that
    key decisions on "has this program shape compiled before" (the sweep
    server's shape-class registry) compare epochs to invalidate their
    seen-sets exactly when the jit caches they mirror are dropped."""
    return _CACHE_EPOCH


def trace_count(reset: bool = False) -> int:
    """Number of times the engine core has been traced (== XLA compiles,
    since the python body runs once per jit cache miss) since import or
    the last reset. `reset=True` returns the current count and zeroes it;
    `clear_cache()` also zeroes it, so compile-count tests can write
    `clear_cache(); ...; assert trace_count() == 1`."""
    return _TRACES.value(reset)


def clear_cache() -> bool:
    """Drop the engine's compiled-program caches (compile-count tests,
    cold benchmark timings) and reset the trace counter. Returns False on
    JAX versions without jit clear_cache support — callers should then
    skip compile-count asserts."""
    global _CACHE_EPOCH
    _TRACES.value(reset=True)
    _CACHE_EPOCH += 1
    cleared = False
    for fn in (_mc_core, _mc_stats, _mc_moments_merge):
        if hasattr(fn, "clear_cache"):
            fn.clear_cache()
            cleared = True
    return cleared


# --------------------------------------------------------------------------
# compiled core
# --------------------------------------------------------------------------
_STATIC_ARGNAMES = (
    "grad_fn", "risk_fn", "row_based", "algo_set", "fading", "steps",
    "n_sizes", "n_antennas", "m_sizes", "invert_channel", "h_min",
    "n_shards", "row_shards", "sgrad_fn", "b_max", "ota_impl", "rng_plan",
    "phase_zero", "sample_idx_fn", "sgrad_idx_fn", "participation_on",
)


def _f32_matmuls(fn):
    """Trace `fn` with every f32 matmul at full f32 precision. The engine
    simulates f32 arithmetic that its references (the CPU reference
    simulators, the Theorem-1 bound) assume; the TPU's default precision
    multiplies f32 matrices in bf16 (3e-3 relative error on the vmapped
    (32, 90) x (90, 500) gradient product on a TPU v5e), which moved the
    Fig. 3 mean curves 4.9e-4 relative from the CPU run (5.0e-6 with this
    setting). The setting is read at trace time, so it rides inside the
    jitted program and leaves the caller's own default untouched."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)
    return wrapped


@_f32_matmuls
def _mc_core_impl(params, betas, theta0, seeds, data, *, grad_fn, risk_fn,
                  row_based, algo_set, fading, steps, n_sizes, n_antennas,
                  m_sizes, invert_channel, h_min, n_shards, row_shards=1,
                  sgrad_fn=None, b_max=0, ota_impl="inline",
                  rng_plan="hoisted", phase_zero=False, sample_idx_fn=None,
                  sgrad_idx_fn=None, participation_on=False,
                  reduce_moments=False):
    """(C,)-batched rows × (S,) seeds × scan(steps), placed on a 2-D
    ("rows", "mc") device mesh when `n_shards > 0` or `row_shards > 1`.

    `algo_set` is the deduped algorithm tuple; the row-to-algorithm
    assignment is traced data (params['algo_idx']), so re-assigning rows
    among the same algorithms reuses the compiled program. Rows sharing one
    algorithm skip the dispatch switch. The momentum carry unifies all step
    rules: m_{k+1} = γ m_k + v_k and θ_{k+1} = θ_k − β m_{k+1} reduce
    bit-exactly to vanilla GD at γ = 0 (0·m = 0, 0 + v = v), and the
    Nesterov lookahead θ − nest·βγ·m is exactly θ when the row's nest flag
    is 0.

    When `algo_set` contains an error-feedback algorithm (`blind_ec`) the
    scan carry additionally holds the per-node residual e (n_max, d): rows
    flagged p['ec']=1 transmit x = α(g + e) with the power-budget scaling
    α = min(1, √(B/‖g+e‖²)) per node and carry e ← (g+e) − x forward
    (error accumulation of 1907.09769); all other rows select α = 1 and
    reduce bit-exactly to x = g — even when their own α expression is NaN
    (an overflowing row under the default unbounded budget hits inf/inf).
    The transmitted energy is always computed from x — identical to the
    g-based accounting whenever no truncation happened.

    `sgrad_fn` (static; a registered `stochastic_grad_row`) switches the
    gradient to a per-slot minibatch: each step consumes one key of the
    dedicated data-key stream and the row's traced params['b_count'] (an
    int32 lane count) picks how many of the static `b_max` index lanes
    count. Under the hoisted plan the index draws move out of the scan via
    the registered `sample_idx_fn` / `sgrad_idx_fn` split, when available.

    `rng_plan` selects the execution strategy (see the module docstring):
    'hoisted' feeds the algorithm's pre-materialized draw streams to the
    scan as inputs — homogeneous (single-algorithm) calls only, since a
    mixed batch would materialize every algorithm's streams per
    trajectory; mixed calls and 'inscan' run the legacy in-scan body,
    kept as the benchmark baseline.

    `reduce_moments` (python-level, not a jit argname: the jitted
    wrappers pin it at their call sites) switches the return value from
    per-seed (risks, cum_energy) to exact two-pass block moments
    (mean, M2) of shape (C, steps+1), reduced INSIDE the mapped region —
    per-shard moments tree-reduce across the 'mc' axis with Chan's
    multi-group merge under `lax.psum`, so only (C, steps+1) statistics
    cross device boundaries regardless of placement.
    """
    _TRACES.add()  # python side effect: runs once per trace/compile

    use_ec = any(ALGO_REGISTRY[a].error_feedback for a in algo_set)
    # The hoisted plan applies to HOMOGENEOUS calls only: with several
    # algorithms dispatched per row by the traced algo_idx switch, every
    # trajectory would have to materialize every algorithm's streams (the
    # switch branch is data, unknowable at trace time) — multiplying draw
    # memory and threefry work by |algo_set| where the in-scan switch
    # executes only the selected branch. Mixed-algo calls (the fig4/fig5/
    # fig7/fig8 comparison shape) therefore keep the legacy in-scan body
    # byte-for-byte; single-algo calls — the large-throughput regime the
    # execution layer targets — hoist everything.
    hoist = rng_plan == "hoisted" and len(algo_set) == 1
    hoist_idx = (hoist and sgrad_fn is not None
                 and sample_idx_fn is not None and sgrad_idx_fn is not None)

    def trajectory(p, beta, row, seed, t0):
        key = jax.random.key(seed)
        n_max_ = row["mask"].shape[0]
        dim = t0.shape[0]

        def make_ctx(draws=None):
            return SlotCtx(fading=fading, p=p, mask=row["mask"],
                           n_sizes=n_sizes, n_antennas=n_antennas,
                           m_sizes=m_sizes, invert_channel=invert_channel,
                           h_min=h_min, ota_impl=ota_impl,
                           phase_zero=phase_zero, draws=draws)

        def slot(g, k, dr_all):
            def ctx_for(a):
                return make_ctx(dr_all.get(a) if dr_all is not None else None)

            if len(algo_set) == 1:
                return ALGO_REGISTRY[algo_set[0]].slot_fn(
                    g, k, ctx_for(algo_set[0]))
            branches = [
                (lambda kk, a=a: ALGO_REGISTRY[a].slot_fn(g, kk, ctx_for(a)))
                for a in algo_set
            ]
            return jax.lax.switch(p["algo_idx"], branches, k)

        def body(carry, x):
            k, dk, dr_all, idx, pu = x
            if use_ec:
                theta, m, e_res, cum_e = carry
            else:
                theta, m, cum_e = carry
            theta_eval = theta - p["nest"] * beta * p["gamma"] * m
            with jax.named_scope("repro.mc.grad"):
                if sgrad_fn is not None:
                    if idx is not None:
                        g = sgrad_idx_fn(row, theta_eval, idx,
                                         p["b_count"])
                    else:
                        g = sgrad_fn(row, theta_eval, dk, p["b_count"],
                                     b_max)
                else:
                    g = (grad_fn(row, theta_eval) if row_based
                         else grad_fn(theta_eval))
            with jax.named_scope("repro.mc.risk"):
                risk = risk_fn(row, theta) if row_based else risk_fn(theta)
            if use_ec:
                u = g + p["ec"] * e_res
                sq = jnp.sum(u * u, axis=1)
                alpha = jnp.minimum(1.0, jnp.sqrt(
                    p["tx_budget"] / jnp.maximum(sq, 1e-30)))
                # select, don't blend: inf/inf above is NaN (e.g. an
                # overflowing row with the default unbounded budget) and
                # 0*NaN would leak it into ec=0 rows
                alpha = jnp.where(p["ec"] > 0, alpha, 1.0)
                x_tx = alpha[:, None] * u
            else:
                x_tx = g
            if participation_on:
                # per-step Bernoulli node mask: a dropped node transmits
                # nothing this slot (and spends no energy); the edge still
                # normalizes by the full N — graceful degradation, not
                # participant-aware rescaling
                x_tx = (pu < p["participation"]).astype(
                    jnp.float32)[:, None] * x_tx
            if use_ec:
                # residual sees the MASKED transmission: a dropped node
                # carries its whole update forward as error feedback
                e_res = p["ec"] * (u - x_tx)
            with jax.named_scope("repro.mc.energy"):
                cum_e = cum_e + p["energy"] * jnp.sum(
                    x_tx.astype(jnp.float32) ** 2)
            with jax.named_scope("repro.mc.slot"):
                v = slot(x_tx, k, dr_all)
            m = p["gamma"] * m + v
            theta = theta - beta * m
            carry = (theta, m, e_res, cum_e) if use_ec \
                else (theta, m, cum_e)
            return carry, (risk, cum_e)

        carry0 = (t0, jnp.zeros_like(t0), jnp.float32(0.0))
        if use_ec:
            carry0 = (t0, jnp.zeros_like(t0),
                      jnp.zeros((row["mask"].shape[0], t0.shape[0]),
                                jnp.float32), jnp.float32(0.0))
        with jax.named_scope("repro.mc.draws"):
            step_keys = jax.random.split(key, steps)
            data_keys = None
            if sgrad_fn is not None:
                data_keys = jax.random.split(
                    jax.random.fold_in(key, _DATA_STREAM), steps)
            draws_all = None
            idx_all = None
            if hoist:
                # The universal RNG plan: every registered stream materializes
                # as one batched (steps, ...) draw outside the scan, via each
                # algorithm's hoist_draws twin. Streams replay the in-scan
                # key-split chains exactly, so the plans are interchangeable.
                ctx0 = make_ctx()
                draws_all = {}
                for a in algo_set:
                    hd = ALGO_REGISTRY[a].hoist_draws
                    if hd is not None:
                        draws_all[a] = hd(step_keys, ctx0, n_max_, dim)
                if not draws_all:
                    # algorithm registered without a hoist twin: nothing was
                    # hoisted — fall through to the legacy in-scan body
                    draws_all = None
                if hoist_idx:
                    idx_all = jax.vmap(
                        lambda dk: sample_idx_fn(row, dk, b_max))(data_keys)
            part_u = None
            if participation_on:
                # the mask stream is hoisted under EVERY rng plan (one
                # code path): a batched uniform over
                # `split(fold_in(key, part), steps)` is stream-identical to
                # per-step in-scan draws over the same keys, and the body
                # stays pure linear algebra
                part_keys = jax.random.split(
                    jax.random.fold_in(key, _PART_STREAM), steps)
                part_u = jax.vmap(lambda pk: jax.random.uniform(
                    pk, (n_max_,), jnp.float32))(part_keys)
        # the scan's own work (the state update, each step's slice of the
        # hoisted draws, the stacked outputs) is `repro.mc.scan`; the
        # body's scopes name the rest
        with jax.named_scope("repro.mc.scan"):
            carry_fin, (risks, cum_e) = jax.lax.scan(
                body, carry0,
                (step_keys, data_keys, draws_all, idx_all, part_u))
        theta_fin = carry_fin[0]
        with jax.named_scope("repro.mc.risk"):
            fin = risk_fn(row, theta_fin) if row_based \
                else risk_fn(theta_fin)
        risks = jnp.concatenate([risks, fin[None]])
        return risks, cum_e  # (steps+1,), (steps,)

    placed = n_shards > 0 or row_shards > 1
    mc_size = max(n_shards, 1)

    def seed_block(seeds_blk, params, betas, theta0, data):
        per_config = jax.vmap(
            lambda p, b, row: jax.vmap(
                lambda s: trajectory(p, b, row, s, theta0))(seeds_blk))
        risks, cum_e = per_config(params, betas, data)
        if not reduce_moments:
            return risks, cum_e
        # exact two-pass moments of this device's seed block, then Chan's
        # multi-group merge across the 'mc' axis: the psum'd correction
        # s_loc·(local_mean − global_mean)² turns per-shard M2 into the
        # global M2 without any per-seed value crossing devices
        s_loc = risks.shape[1]
        lsum = jnp.sum(risks, axis=1)
        lmean = lsum / s_loc
        lm2 = jnp.sum(jnp.square(risks - lmean[:, None, :]), axis=1)
        if placed:
            gmean = jax.lax.psum(lsum, "mc") / (s_loc * mc_size)
            gm2 = jax.lax.psum(
                lm2 + s_loc * jnp.square(lmean - gmean), "mc")
            return gmean, gm2
        return lmean, lm2

    if placed:
        mesh = compat.make_mesh((row_shards, mc_size), ("rows", "mc"))
        if reduce_moments:  # moments leave the region 'mc'-replicated
            out_specs = (P("rows"), P("rows"))
        else:
            out_specs = (P("rows", "mc"), P("rows", "mc"))
        # check_vma=False: the scan carries start from the replicated
        # theta0 and turn device-varying after one step; every collective
        # in the region is an explicit psum over 'mc', so the varying-axes
        # type check has nothing left to protect
        seed_block = compat.shard_map(
            seed_block, mesh=mesh,
            in_specs=(P("mc"), P("rows"), P("rows"), P(), P("rows")),
            out_specs=out_specs, check_vma=False)
    return seed_block(seeds, params, betas, theta0, data)


_mc_core = jax.jit(_mc_core_impl, static_argnames=_STATIC_ARGNAMES)


@functools.partial(jax.jit, static_argnames=_STATIC_ARGNAMES)
def _mc_stats(params, betas, theta0, seeds, data, **kw):
    """Single-shot on-device seed reduction (`keep_seed_curves=False`,
    `seed_chunk=None`): the (C, S, steps+1) curves stay device-side; only
    the (C, steps+1) mean and ci95 transfer. Exact two-pass moments —
    the same formula the host path applies to materialized curves."""
    risks, _ = _mc_core_impl(params, betas, theta0, seeds, data, **kw)
    n = risks.shape[1]
    mean = jnp.mean(risks, axis=1)
    if n > 1:
        ci95 = 1.96 * jnp.std(risks, axis=1, ddof=1) / np.sqrt(n)
    else:
        ci95 = jnp.zeros_like(mean)
    return mean, ci95


def chan_merge(mean_a, m2_a, n_a, mean_b, m2_b, n_b):
    """Chan's parallel-variance merge of two (mean, M2, n) moment groups.

    M2 is the centered sum of squares Σ(x − mean)²; the merge is exact in
    exact arithmetic and numerically stable where the one-pass
    (Σx, Σx²) accumulator catastrophically cancels (variance far below
    the squared mean). With n_a = 0 the result is group b exactly:
    delta·n_b/n = mean_b and the cross term vanishes, so the first chunk
    of a sweep is bit-identical to its own two-pass moments.

    Works elementwise on arrays and under jit/np alike; `n_a`/`n_b` may
    be traced scalars (chunk counts are data, not compile-time shape).
    """
    n = n_a + n_b
    delta = mean_b - mean_a
    mean = mean_a + delta * (n_b / n)
    m2 = m2_a + m2_b + jnp.square(delta) * (n_a * n_b / n)
    return mean, m2


@functools.partial(jax.jit, static_argnames=_STATIC_ARGNAMES,
                   donate_argnums=(0, 1))
def _mc_moments_merge(acc_mean, acc_m2, n_prev, params, betas, theta0,
                      seeds, data, **kw):
    """One seed chunk's exact two-pass block moments Chan-merged into the
    running (mean, M2) curve statistics. The accumulators are DONATED:
    XLA reuses their buffers in place, so the chunked stats path carries
    O(C · steps) state between chunks and nothing else survives a chunk.
    `n_prev` is traced data (float32) — the chunk cursor never recompiles.
    """
    bmean, bm2 = _mc_core_impl(params, betas, theta0, seeds, data,
                               reduce_moments=True, **kw)
    n_b = jnp.float32(seeds.shape[0])
    return chan_merge(acc_mean, acc_m2, n_prev, bmean, bm2, n_b)


def host_seed_stats(risks: np.ndarray) -> tuple:
    """(C, S, steps+1) curves -> (mean, ci95), the host-side seed
    reduction — the single definition the unchunked, chunked and
    on-device paths all agree with."""
    seeds = risks.shape[1]
    mean = np.mean(risks, axis=1)
    if seeds > 1:
        ci95 = 1.96 * np.std(risks, axis=1, ddof=1) / np.sqrt(seeds)
    else:
        ci95 = np.zeros_like(mean)
    return mean, ci95


def finalize_merged_stats(mean: np.ndarray, m2: np.ndarray,
                          n_seeds: int) -> tuple:
    """Chan-merged (mean, M2, n) -> (mean, ci95), ddof=1 sample variance.

    M2 = Σ(x − mean)² is nonnegative by construction (up to rounding in
    the merge's cross terms, hence the max with 0) — unlike the retired
    one-pass (Σx, Σx²) accumulator, whose difference of large squares
    collapsed ci95 to 0 on near-deterministic rows.
    """
    if n_seeds > 1:
        var = np.maximum(0.0, np.asarray(m2)) / (n_seeds - 1)
        ci95 = 1.96 * np.sqrt(var / n_seeds)
    else:
        ci95 = np.zeros_like(mean)
    return np.asarray(mean), ci95


# --------------------------------------------------------------------------
# seed-chunked scheduler (+ resume + chunk-level fault isolation)
# --------------------------------------------------------------------------
_RESUME_FILE = "mc_chunked_resume.npz"

# Fault-injection seam: hooks fire at the START of every chunk attempt
# with {"off": int, "attempt": int, "stage": "moments"|"curves"}; a hook
# that raises simulates that chunk failing (tests/_fault_harness.py
# schedules deterministic fault patterns through this).
_CHUNK_FAULT_HOOKS = []


def install_chunk_fault_hook(hook):
    """Register a chunk-attempt hook (fault injection); returns a
    remover callable. Hooks see every attempt of every chunk and may
    raise to make that attempt fail."""
    _CHUNK_FAULT_HOOKS.append(hook)

    def remove():
        try:
            _CHUNK_FAULT_HOOKS.remove(hook)
        except ValueError:
            pass
    return remove


def _attempt_chunk(retry, off, stage, attempt_fn, reset_fn=None,
                   span=None):
    """Run one chunk with the plan's `RetryPolicy`: on an exception the
    accumulator state is rolled back (`reset_fn`), the policy's capped
    exponential backoff waits, and the chunk re-runs — replaying its
    exact counter-based streams, so a retried chunk is indistinguishable
    from a first-try one. `retry=None` (or an exhausted budget)
    re-raises: fail-fast is the legacy behavior and the checkpoint on
    disk stays at the last completed chunk. The attempt that succeeded
    is recorded on the chunk's `span` as its `attempt` stat."""
    attempt = 1
    while True:
        try:
            for hook in list(_CHUNK_FAULT_HOOKS):
                hook({"off": int(off), "attempt": attempt, "stage": stage})
            out = attempt_fn()
            if span is not None:
                span.set_metadata(attempt=attempt)
            return out
        except Exception:
            if retry is None or attempt >= retry.max_attempts:
                raise
            if reset_fn is not None:
                reset_fn()
            retry.wait(attempt)
            attempt += 1


def _hash_array_leaf(h, name, value) -> None:
    arr = np.asarray(value)
    h.update(f"{name}:{arr.dtype.str}:{arr.shape};".encode())
    h.update(np.ascontiguousarray(arr).tobytes())


def _hash_static_kwargs(h, statics: dict) -> None:
    """Feed STATIC facets (jit static_argnames material) into a hash:
    callables by qualname — stable across processes, unlike their reprs —
    everything else by repr. Shared by the resume fingerprint and the
    serving compile signature."""
    for name in sorted(statics):
        v = statics[name]
        if callable(v):
            v = getattr(v, "__qualname__", repr(v))
        h.update(f"{name}={v!r};".encode())


def static_signature(statics: dict) -> str:
    """Compile-cache signature of one engine call: a sha256 hex digest
    over static facets only — shapes, flags, registry callables — the
    things `_mc_core`'s jit cache keys on. Values may be numbers,
    strings, bools, tuples or callables; array-valued workload data does
    NOT belong here (rows that differ only in data share a signature —
    that is the whole point). Two calls with equal signatures trace the
    same compiled program, so a serving router
    (`repro.serving.mc_server`) can coalesce them into one padded batch
    and pay exactly one compile."""
    h = hashlib.sha256()
    _hash_static_kwargs(h, statics)
    return h.hexdigest()


def _workload_fingerprint(params, betas, theta0, seed_ints, data,
                          seed_chunk, n_rows, n_shards, row_shards,
                          core_kwargs) -> np.ndarray:
    """sha256 identity of a chunked sweep, as a (32,) uint8 leaf.

    Covers the static core kwargs (callables by qualname — stable across
    processes, unlike their reprs), the numeric workload (channel/algo
    params, stepsizes, theta0, problem data — a different noise_std or
    stepsize is a different sweep even though every static matches), the
    full seed-int sequence, the chunk size, the row count and the mesh
    shape. Two sweeps with equal fingerprints replay identical chunk
    streams in identical order, so a checkpoint from one resumes the
    other bit-identically; placement is included because the
    cross-device moment reduction order is part of the accumulators'
    bit pattern.
    """
    h = hashlib.sha256()
    _hash_static_kwargs(h, core_kwargs)
    for name in sorted(params):
        _hash_array_leaf(h, f"params.{name}", params[name])
    for name in sorted(data):
        _hash_array_leaf(h, f"data.{name}", data[name])
    _hash_array_leaf(h, "betas", betas)
    _hash_array_leaf(h, "theta0", theta0)
    h.update(np.ascontiguousarray(
        np.asarray(seed_ints, np.int64)).tobytes())
    h.update(f"chunk={seed_chunk};rows={n_rows};"
             f"mesh={row_shards}x{n_shards};".encode())
    return np.frombuffer(h.digest(), np.uint8)


def _chunk_span(call: int, chunk: int):
    return jax.profiler.TraceAnnotation("repro.run_mc.chunk", call=call,
                                        chunk=chunk)


def run_chunked(params, betas, theta0, seed_ints, data, *, seed_chunk,
                keep_seed_curves, n_shards, row_shards=1, core_kwargs,
                resume_dir=None, retry=None, call=0):
    """Drive the seed axis in blocks of `seed_chunk` through one compiled
    program (chunk seed ints are data). Returns the same
    (risks, cum_energy, mean, ci95) quadruple as the single-shot paths,
    with the first two None when `keep_seed_curves=False`.

    Per-chunk peak memory is O(C · seed_chunk · steps · n_max): the
    hoisted RNG streams re-materialize per chunk, per-seed curves either
    stream to preallocated host arrays (`keep_seed_curves=True`) or
    Chan-merge into donated (C, steps+1) moment accumulators.
    `n_shards`/`row_shards` place each chunk on the ("rows", "mc") mesh.

    `resume_dir` (moments path only) persists (fingerprint, chunk
    cursor, acc_mean, acc_m2) to `<resume_dir>/mc_chunked_resume.npz`
    after every chunk, and restores from it when present: the sweep
    restarts at the first unfinished chunk with the saved accumulators.
    Counter-based RNG replays each chunk's streams exactly and the f32
    host round-trip is value-preserving, so interrupted-then-resumed
    equals uninterrupted bit-for-bit. A checkpoint written by a
    different workload (fingerprint mismatch) raises instead of
    silently corrupting the sweep; a finished sweep's checkpoint
    short-circuits straight to finalization. A CORRUPT checkpoint
    (truncated, bit-flipped — `ckpt.CheckpointCorrupt`) falls back to
    the rotated `.prev` artifact, and when both are bad the sweep
    restarts from scratch with a warning — never a crash, never a
    silent resume from garbage.

    `retry` (a `plan.RetryPolicy`) adds chunk-level fault isolation: a
    chunk that raises is rolled back and re-attempted with capped
    exponential backoff; counter-based RNG replays its exact streams, so
    a sweep surviving k faults within budget is bit-identical to the
    fault-free run.

    Each chunk, with its retries and its transfer to the host, is one
    `repro.run_mc.chunk` span carrying `call` (the `run_mc` call's
    number), `chunk` (its index) and `attempt`.
    """
    seeds = len(seed_ints)
    if seed_chunk <= 0:
        raise ValueError(f"seed_chunk must be positive, got {seed_chunk}")
    if seeds % seed_chunk != 0:
        raise ValueError(
            f"seeds ({seeds}) must divide into seed_chunk ({seed_chunk}) "
            "blocks — pad the seed count or pick a chunk that divides it")
    steps = core_kwargs["steps"]
    n_rows = len(betas)
    if keep_seed_curves:
        if resume_dir is not None:
            raise ValueError(
                "resume_dir requires the reduced-moments path "
                "(keep_seed_curves=False): per-seed curves are not "
                "checkpointed between chunks")
        risks = np.empty((n_rows, seeds, steps + 1), np.float32)
        cum_e = np.empty((n_rows, seeds, steps), np.float32)
        for off in range(0, seeds, seed_chunk):
            blk = jnp.asarray(seed_ints[off:off + seed_chunk])

            def _run(blk=blk):
                return _mc_core(params, betas, theta0, blk, data,
                                n_shards=n_shards, row_shards=row_shards,
                                **core_kwargs)

            with _chunk_span(call, off // seed_chunk) as span:
                r, ce = _attempt_chunk(retry, off, "curves", _run,
                                       span=span)
                risks[:, off:off + seed_chunk] = np.asarray(r)
                cum_e[:, off:off + seed_chunk] = np.asarray(ce)
        return (risks, cum_e) + host_seed_stats(risks)
    fp = _workload_fingerprint(params, betas, theta0, seed_ints, data,
                               seed_chunk, n_rows, n_shards, row_shards,
                               core_kwargs)
    start = 0
    acc_mean = jnp.zeros((n_rows, steps + 1), jnp.float32)
    acc_m2 = jnp.zeros((n_rows, steps + 1), jnp.float32)
    ckpt_path = None
    if resume_dir is not None:
        ckpt_path = os.path.join(resume_dir, _RESUME_FILE)
        candidates = [p for p in (ckpt_path, ckpt_path + ckpt.PREV_SUFFIX)
                      if os.path.exists(p)]
        raw = None
        for cand in candidates:
            try:
                raw = ckpt.peek(cand)
                break
            except ckpt.CheckpointCorrupt as e:
                # fall back to the rotated artifact; a torn newest write
                # costs at most one chunk of progress
                import warnings
                warnings.warn(f"ignoring corrupt resume checkpoint: {e}")
        if raw is not None:
            if not np.array_equal(raw.get("fingerprint"), fp):
                raise ValueError(
                    f"checkpoint at {ckpt_path} belongs to a different "
                    "workload (fingerprint mismatch) — point resume_dir "
                    "at this sweep's own directory or remove the stale "
                    "checkpoint")
            start = int(raw["next_off"])
            acc_mean = jnp.asarray(raw["acc_mean"])
            acc_m2 = jnp.asarray(raw["acc_m2"])
        elif candidates:
            import warnings
            warnings.warn(
                f"no intact resume checkpoint under {resume_dir} — "
                "restarting the sweep from the first chunk")
    for off in range(start, seeds, seed_chunk):
        blk = jnp.asarray(seed_ints[off:off + seed_chunk])

        def _merge(blk=blk, off=off):
            return _mc_moments_merge(
                acc_mean, acc_m2, np.float32(off), params, betas, theta0,
                blk, data, n_shards=n_shards, row_shards=row_shards,
                **core_kwargs)

        with _chunk_span(call, off // seed_chunk) as span:
            # the merge DONATES the accumulators: for retry, snapshot them
            # to host first so a failed attempt can roll back (the f32
            # round-trip is value-preserving — bit-identity holds)
            snap = (np.asarray(acc_mean), np.asarray(acc_m2)) \
                if retry is not None else None

            def _reset(snap=snap):
                nonlocal acc_mean, acc_m2
                acc_mean = jnp.asarray(snap[0])
                acc_m2 = jnp.asarray(snap[1])

            acc_mean, acc_m2 = _attempt_chunk(
                retry, off, "moments", _merge,
                _reset if retry is not None else None, span=span)
            if ckpt_path is not None:
                # np.asarray copies to host BEFORE the next merge donates
                # the accumulator buffers back to XLA
                ckpt.save(ckpt_path, {
                    "fingerprint": fp,
                    "next_off": np.int64(off + seed_chunk),
                    "acc_mean": np.asarray(acc_mean),
                    "acc_m2": np.asarray(acc_m2)})
    mean, ci95 = finalize_merged_stats(
        np.asarray(acc_mean), np.asarray(acc_m2), seeds)
    return None, None, mean, ci95


# --------------------------------------------------------------------------
# analytic memory model
# --------------------------------------------------------------------------
_F32 = 4  # bytes


def estimate_peak_bytes(*, n_rows: int, seeds: int, steps: int, n_max: int,
                        dim: int, algo_set=("gbma",), seed_chunk=None,
                        n_antennas=None, m_sizes=(), b_max: int = 0,
                        keep_seed_curves: bool = True,
                        rng_plan: str = "hoisted",
                        invert_channel: bool = False,
                        participation_on: bool = False,
                        n_shards: int = 1, row_shards: int = 1) -> dict:
    """Analytic peak-memory estimate (bytes) of one engine call, per the
    execution-layer memory model (docs/performance.md).

    Counts the O(C · S_live · steps)-scaling buffers that dominate at
    scale — the hoisted per-stream RNG draws (per-algorithm widths from
    `slots.hoist_draw_elems`, next to the registry), the scanned per-seed
    curve outputs, and the per-step gradient temporaries — for S_live =
    seed_chunk (when chunking) or the full seed count. Deliberately an
    estimate: XLA fusion removes some temporaries and adds others, so
    treat it as the scaling model the knobs are chosen against, not an
    allocator ground truth.

    Under placement every counted buffer is sharded over the
    (row_shards × n_shards) mesh — each device materializes only its own
    seeds' streams — so `per_device_peak_bytes` is the whole-call total
    divided by the mesh size; it is the figure `plan.auto_plan` sizes
    chunks against.
    """
    from repro.core.mc import slots

    s_live = seeds if seed_chunk is None else min(seed_chunk, seeds)
    m_live = max(m_sizes) if m_sizes else (n_antennas or 1)
    per_traj_draws = 0
    # draws hoist only on homogeneous calls (see _mc_core_impl)
    if rng_plan == "hoisted" and len(algo_set) == 1:
        for a in algo_set:
            per_traj_draws += slots.hoist_draw_elems(
                a, steps=steps, n_max=n_max, dim=dim, m_live=m_live,
                invert_channel=invert_channel)
        if b_max > 0:
            per_traj_draws += steps * n_max * b_max  # minibatch indices
    if participation_on:
        # the node-dropout mask stream hoists under EVERY rng plan
        per_traj_draws += steps * n_max
    draw_bytes = n_rows * s_live * per_traj_draws * _F32
    # scanned outputs: risks (steps+1) + cum_energy (steps) per trajectory
    curve_bytes = n_rows * s_live * (2 * steps + 1) * _F32
    # per-step live temporaries: transmitted g + one working copy
    temp_bytes = 2 * n_rows * s_live * n_max * dim * _F32
    host_bytes = (n_rows * seeds * (2 * steps + 1) * _F32
                  if keep_seed_curves else 0)
    device_total = draw_bytes + curve_bytes + temp_bytes
    mesh_size = max(n_shards, 1) * max(row_shards, 1)
    return {
        "device_peak_bytes": device_total,
        "per_device_peak_bytes": -(-device_total // mesh_size),
        "rng_draw_bytes": draw_bytes,
        "curve_bytes": curve_bytes,
        "grad_temp_bytes": temp_bytes,
        "host_curve_bytes": host_bytes,
        "s_live": s_live,
    }
