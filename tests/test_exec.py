"""Execution-layer (`repro.core.mc.exec`) semantics:

  * RNG-plan stream equivalence: every algorithm's `hoist_draws` twin is
    BIT-identical to the per-slot in-scan draw chain it replaces, across
    fading families × antenna modes × phase settings (property test), and
    the hoisted minibatch-index stream matches the in-scan index draws;
  * trajectory equivalence: `rng_plan='hoisted'` == `'inscan'` across
    algo families × stochastic on/off (identical streams; only XLA fusion
    rounding may differ);
  * seed chunking: chunked curves match unchunked (1e-6 criterion),
    chunk validation errors, and the donated-stats path matches the host
    reduction; `keep_seed_curves=False` returns (mean, ci95) only and
    `energy_to_target` refuses reduced results;
  * `params['b_count']` is int32: lane counts at 2^24-scale survive
    exactly (the float32 carry they replace does not), and the engine
    hands an integer lane count to the stochastic gradient;
  * `trace_count(reset=)` / `clear_cache()` bookkeeping;
  * `estimate_peak_bytes` scaling sanity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from benchmarks.common import MSDProblem
from repro.core.channel import ChannelConfig
from repro.core.mc import exec as exec_mod
from repro.core.mc import problems as prob_mod
from repro.core.mc import sampling
from repro.core.mc.exec import estimate_peak_bytes
from repro.core.mc.slots import ALGO_REGISTRY, SlotCtx
from repro.core.montecarlo import (clear_cache, energy_to_target,
                                   logistic_mc_problem, run_mc, trace_count)
from repro.data.synthetic import logistic_classification

N, D, STEPS, SEEDS = 14, 10, 12, 4


@pytest.fixture(scope="module")
def mc():
    return MSDProblem.make(N, dim=D).to_mc()


@pytest.fixture(scope="module")
def logistic_prob():
    X, y, _ = logistic_classification(60, dim=8, seed=3)
    return logistic_mc_problem(X, y, 10, lam=0.1)


def _ch(**kw):
    kw.setdefault("fading", "rayleigh")
    kw.setdefault("noise_std", 0.5)
    return ChannelConfig(**kw)


def _row_params(fading, phase, m_row=None):
    p = {"scale": jnp.float32(0.9), "rician_k": jnp.float32(3.0),
         "phase_error_max": jnp.float32(phase),
         "noise_std": jnp.float32(0.5), "energy": jnp.float32(0.7),
         "n_nodes": jnp.float32(N)}
    if m_row is not None:
        p["n_antennas"] = jnp.float32(m_row)
    return p


def _ctx(fading, phase, *, n_antennas=None, m_sizes=(), invert=False,
         m_row=None):
    return SlotCtx(fading=fading, p=_row_params(fading, phase, m_row),
                   mask=jnp.ones((N,), jnp.float32), n_sizes=(N,),
                   n_antennas=n_antennas, m_sizes=m_sizes,
                   invert_channel=invert, h_min=0.3,
                   phase_zero=(phase == 0.0))


def _inscan_ota_draw(key, ctx):
    """The draw chain `_ota_slot` runs in-scan (phase stream included)."""
    k_h, k_w = jax.random.split(key)
    h = sampling._row_gains(k_h, ctx.fading, ctx.p, ctx.n_sizes, N)
    return h, jax.random.normal(k_w, (D,), jnp.float32)


@settings(max_examples=16, deadline=None)
@given(fading=st.sampled_from(["equal", "rayleigh", "rician", "lognormal"]),
       phase=st.sampled_from([0.0, 0.4]),
       mode=st.sampled_from(["single", "static_m", "per_row_m"]),
       seed=st.integers(0, 2**16))
def test_gbma_hoist_streams_bit_identical_to_inscan(fading, phase, mode,
                                                    seed):
    """`_gbma_hoist_draws` replays the in-scan k → antennas → (k_h, k_w)
    chain bit-for-bit — including the static phase-zero shortcut (cos(0)
    is exactly 1, so skipping the phase stream changes no value)."""
    if mode == "single":
        ctx = _ctx(fading, phase)
    elif mode == "static_m":
        ctx = _ctx(fading, phase, n_antennas=3)
    else:
        ctx = _ctx(fading, phase, m_sizes=(2, 4), m_row=2)
    step_keys = jax.random.split(jax.random.key(seed), 5)
    draws = ALGO_REGISTRY["gbma"].hoist_draws(step_keys, ctx, N, D)
    for t in range(5):
        if mode == "single":
            akeys = [step_keys[t]]
        elif mode == "static_m":
            akeys = list(jax.random.split(step_keys[t], 3))
        else:
            akeys = list(sampling._antenna_keys(step_keys[t], (2, 4)))
        for a_i, ak in enumerate(akeys):
            h, w = _inscan_ota_draw(ak, ctx)
            got_h = draws.get("h")
            got_w = draws["w"]
            sel = (lambda x: x[t]) if mode == "single" \
                else (lambda x: x[t, a_i])
            if got_h is None:
                assert ctx.fading == "equal" and ctx.phase_zero
            else:
                np.testing.assert_array_equal(np.asarray(sel(got_h)),
                                              np.asarray(h))
            np.testing.assert_array_equal(np.asarray(sel(got_w)),
                                          np.asarray(w))


@settings(max_examples=12, deadline=None)
@given(fading=st.sampled_from(["equal", "rayleigh", "rician", "lognormal"]),
       seed=st.integers(0, 2**16))
def test_blind_hoist_streams_bit_identical_to_inscan(fading, seed):
    ctx = _ctx(fading, 0.0, n_antennas=3)
    step_keys = jax.random.split(jax.random.key(seed), 4)
    draws = ALGO_REGISTRY["blind"].hoist_draws(step_keys, ctx, N, D)
    for t in range(4):
        for a_i, ak in enumerate(jax.random.split(step_keys[t], 3)):
            k_h, k_w = jax.random.split(ak)
            a, b = sampling._row_complex_gains(k_h, fading, ctx.p,
                                               (N,), N)
            z = jax.random.normal(k_w, (2, D), jnp.float32)
            np.testing.assert_array_equal(np.asarray(draws["a"][t, a_i]),
                                          np.asarray(a))
            np.testing.assert_array_equal(np.asarray(draws["b"][t, a_i]),
                                          np.asarray(b))
            np.testing.assert_array_equal(np.asarray(draws["z"][t, a_i]),
                                          np.asarray(z))


@settings(max_examples=12, deadline=None)
@given(fading=st.sampled_from(["equal", "rayleigh", "lognormal"]),
       invert=st.booleans(), seed=st.integers(0, 2**16))
def test_fdm_and_pc_hoist_streams_bit_identical_to_inscan(fading, invert,
                                                          seed):
    ctx = _ctx(fading, 0.0, invert=invert)
    step_keys = jax.random.split(jax.random.key(seed), 4)
    fdm = ALGO_REGISTRY["fdm"].hoist_draws(step_keys, ctx, N, D)
    pc = ALGO_REGISTRY["power_control"].hoist_draws(step_keys, ctx, N, D)
    for t in range(4):
        k_h, k_w = jax.random.split(step_keys[t])
        raw = sampling._row_normal(k_w, ctx.p, (N,), N, D, jnp.float32)
        np.testing.assert_array_equal(np.asarray(fdm["noise_raw"][t]),
                                      np.asarray(raw))
        if not invert and not (fading == "equal" and ctx.phase_zero):
            h = sampling._row_gains(k_h, fading, ctx.p, (N,), N)
            np.testing.assert_array_equal(np.asarray(fdm["h"][t]),
                                          np.asarray(h))
        h_pc, w_pc = _inscan_ota_draw(step_keys[t], ctx)
        if "h" in pc:
            np.testing.assert_array_equal(np.asarray(pc["h"][t]),
                                          np.asarray(h_pc))
        np.testing.assert_array_equal(np.asarray(pc["w"][t]),
                                      np.asarray(w_pc))


def test_minibatch_index_stream_bit_identical(logistic_prob):
    """The hoisted minibatch-index stream == the in-scan per-slot index
    draws (the data-key chain is untouched by hoisting)."""
    spec = prob_mod.PROBLEMS["logistic"]
    batch = prob_mod.MCProblemBatch.stack([logistic_prob])
    row = {k: v[0] for k, v in batch.data.items()}
    key = jax.random.key(5)
    data_keys = jax.random.split(
        jax.random.fold_in(key, exec_mod._DATA_STREAM), 6)
    hoisted = jax.vmap(lambda dk: spec.sample_indices_row(row, dk, 3))(
        data_keys)
    for t in range(6):
        np.testing.assert_array_equal(
            np.asarray(hoisted[t]),
            np.asarray(spec.sample_indices_row(row, data_keys[t], 3)))


@settings(max_examples=10, deadline=None)
@given(fading=st.sampled_from(["equal", "rayleigh", "rician", "lognormal"]),
       algo=st.sampled_from(["gbma", "fdm", "power_control", "momentum",
                             "blind", "blind_ec"]),
       stochastic=st.booleans())
def test_rng_plans_produce_equivalent_trajectories(fading, algo,
                                                   stochastic):
    """hoisted == inscan trajectories: the streams are identical, so any
    difference is XLA fusion rounding (bounded well inside the sweep
    reproduction tolerance)."""
    if stochastic:
        X, y, _ = logistic_classification(48, dim=6, seed=1)
        problem = logistic_mc_problem(X, y, 8, lam=0.1)
        kw = {"batch_frac": 0.5}
        beta = 0.3
    else:
        problem = MSDProblem.make(N, dim=D).to_mc()
        kw = {}
        beta = 0.01
    if algo in ("blind", "blind_ec"):
        kw["n_antennas"] = 2
    ch = _ch(fading=fading)
    r_h = run_mc(problem, [ch], algo, [beta], STEPS, 2, rng_plan="hoisted",
                 **kw)
    r_i = run_mc(problem, [ch], algo, [beta], STEPS, 2, rng_plan="inscan",
                 **kw)
    np.testing.assert_allclose(r_h.risks, r_i.risks, rtol=2e-4, atol=1e-7)
    np.testing.assert_allclose(r_h.cum_energy, r_i.cum_energy, rtol=1e-5,
                               atol=1e-9)


def test_rng_plan_validation(mc):
    with pytest.raises(ValueError, match="rng_plan"):
        run_mc(mc, [_ch()], "gbma", [0.01], 4, 1, rng_plan="fast")


def test_algo_without_hoist_twin_keeps_legacy_nsweep_hoist(monkeypatch):
    """A single-algo call whose algorithm registered no hoist_draws twin
    must fall through to the LEGACY plan — including PR 2's N-sweep gain
    hoist — not run a strictly worse draws-free hoisted program. Byte
    equality with rng_plan='inscan' proves the same program ran."""
    import dataclasses as dc

    from repro.core.mc import slots as slots_mod

    spec = ALGO_REGISTRY["gbma"]
    monkeypatch.setitem(
        slots_mod.ALGO_REGISTRY, "custom_no_twin",
        dc.replace(spec, name="custom_no_twin", hoist_draws=None,
                   theorem1=False))
    probs = [MSDProblem.make(n, dim=8).to_mc() for n in (6, 9)]
    r_h = run_mc(probs, [_ch(), _ch()], "custom_no_twin", [0.01] * 2,
                 STEPS, 2, rng_plan="hoisted")
    r_i = run_mc(probs, [_ch(), _ch()], "custom_no_twin", [0.01] * 2,
                 STEPS, 2, rng_plan="inscan")
    np.testing.assert_array_equal(r_h.risks, r_i.risks)
    # and it matches the registered gbma path (same slot fn, same keys)
    r_g = run_mc(probs, [_ch(), _ch()], "gbma", [0.01] * 2, STEPS, 2,
                 rng_plan="inscan")
    np.testing.assert_array_equal(r_h.risks, r_g.risks)


def test_mixed_algo_calls_keep_the_inscan_body(mc):
    """Hoisting is gated to homogeneous calls: a mixed-algo batch under
    the hoisted plan runs the legacy in-scan body BYTE-for-byte (every
    trajectory would otherwise materialize every algorithm's streams)."""
    algos = ("gbma", "fdm", "centralized")
    r_h = run_mc(mc, [_ch()] * 3, algos, [0.01] * 3, STEPS, 2,
                 rng_plan="hoisted")
    r_i = run_mc(mc, [_ch()] * 3, algos, [0.01] * 3, STEPS, 2,
                 rng_plan="inscan")
    np.testing.assert_array_equal(r_h.risks, r_i.risks)
    np.testing.assert_array_equal(r_h.cum_energy, r_i.cum_energy)


# --------------------------------------------------------------------------
# seed chunking
# --------------------------------------------------------------------------
def test_chunked_matches_unchunked_across_families(mc, logistic_prob):
    """The 1e-6 criterion: chunked curves reproduce the single-shot call
    for every algo family (in practice bit-identical on one device — each
    trajectory depends only on its seed)."""
    cases = [
        (mc, "gbma", 0.01, {}),
        (mc, "fdm", 0.01, {}),
        (mc, "centralized", 0.01, {}),
        (mc, "power_control", 0.01, {}),
        (mc, "nesterov", 0.01, {"momentum": 0.6}),
        (mc, "blind", 0.01, {"n_antennas": 2}),
        (mc, "blind_ec", 0.01, {"n_antennas": 2, "power_budget": 0.05}),
        (logistic_prob, "gbma", 0.3, {"batch_frac": 0.5}),
    ]
    for problem, algo, beta, kw in cases:
        full = run_mc(problem, [_ch()], algo, [beta], STEPS, SEEDS, **kw)
        chunked = run_mc(problem, [_ch()], algo, [beta], STEPS, SEEDS,
                         seed_chunk=2, **kw)
        np.testing.assert_allclose(chunked.risks, full.risks, rtol=1e-6,
                                   atol=1e-10, err_msg=algo)
        np.testing.assert_allclose(chunked.cum_energy, full.cum_energy,
                                   rtol=1e-6, atol=1e-10, err_msg=algo)
        np.testing.assert_allclose(chunked.mean, full.mean, rtol=1e-6,
                                   atol=1e-10, err_msg=algo)


def test_chunked_one_compile(mc):
    """All chunks reuse ONE compiled program (the chunk's seed ints are
    data, not shape)."""
    clear_cache()
    run_mc(mc, [_ch()], "gbma", [0.01], STEPS, 8, seed_chunk=2)
    assert trace_count() == 1


def test_chunk_validation(mc):
    with pytest.raises(ValueError, match="divide"):
        run_mc(mc, [_ch()], "gbma", [0.01], STEPS, 5, seed_chunk=2)
    with pytest.raises(ValueError, match="positive"):
        run_mc(mc, [_ch()], "gbma", [0.01], STEPS, 4, seed_chunk=0)


def test_reduced_stats_match_host_reduction(mc):
    """keep_seed_curves=False (single-shot AND chunked/donated) returns
    the same mean/ci95 the host computes from materialized curves."""
    full = run_mc(mc, [_ch()], "gbma", [0.01], STEPS, SEEDS)
    for kw in ({}, {"seed_chunk": 2}):
        red = run_mc(mc, [_ch()], "gbma", [0.01], STEPS, SEEDS,
                     keep_seed_curves=False, **kw)
        assert red.risks is None and red.cum_energy is None
        np.testing.assert_allclose(red.mean, full.mean, rtol=1e-5,
                                   atol=1e-9)
        np.testing.assert_allclose(red.ci95, full.ci95, rtol=5e-3,
                                   atol=1e-7)
    with pytest.raises(ValueError, match="keep_seed_curves"):
        energy_to_target(
            run_mc(mc, [_ch()], "gbma", [0.01], STEPS, SEEDS,
                   keep_seed_curves=False), 0.1)


def test_single_seed_reduced_stats(mc):
    red = run_mc(mc, [_ch()], "gbma", [0.01], STEPS, 1,
                 keep_seed_curves=False)
    assert np.all(red.ci95 == 0.0)
    full = run_mc(mc, [_ch()], "gbma", [0.01], STEPS, 1)
    np.testing.assert_allclose(red.mean, full.mean, rtol=1e-6)


def test_finalize_merged_stats_deterministic_rows():
    """Deterministic rows: M2 is exactly 0 (centered sums of identical
    values), so ci95 is exactly 0 — no cancellation, no NaN."""
    curves = np.full((1, 5), 0.123456, np.float32)
    m2 = np.zeros_like(curves)
    mean, ci = exec_mod.finalize_merged_stats(curves, m2, 4)
    np.testing.assert_allclose(mean, curves, rtol=1e-6)
    assert np.all(ci == 0.0)


# --------------------------------------------------------------------------
# b_count int32 (satellite)
# --------------------------------------------------------------------------
def test_b_count_survives_2_24_scale(monkeypatch):
    """Lane counts are integral: carried as int32 they survive 2^24-scale
    sample counts exactly; the float32 carry they replace does not. The
    fake kind's sample axis has a zero-size trailing dim, so the
    2^24+1-sample shape allocates nothing."""
    from repro.core.mc.engine import _resolve_batch_frac
    from repro.core.mc.problems import MCProblem

    big_k = 2**24 + 1
    spec = dataclasses.replace(
        prob_mod.PROBLEMS["logistic"], kind="bigk_test")
    monkeypatch.setitem(prob_mod.PROBLEMS, "bigk_test", spec)
    problem = MCProblem(
        grad_fn=lambda t: t[None, :], risk_fn=jnp.sum, dim=2, n_nodes=1,
        kind="bigk_test", data={"Xn": np.zeros((1, big_k, 0))},
        stochastic=True)
    _, b_max, b_counts = _resolve_batch_frac(1.0 - 1e-9, 1, None, problem)
    assert b_counts == (big_k,)
    carried = jnp.asarray(b_counts, jnp.int32)
    assert int(carried[0]) == big_k, "int32 lane count must be exact"
    # the bug this guards against: a float32 carry silently rounds
    assert int(jnp.asarray(b_counts, jnp.float32)[0]) != big_k
    assert b_max == big_k


def test_engine_hands_integer_lane_count_to_sgrad(logistic_prob,
                                                  monkeypatch):
    """The engine's params['b_count'] reaches the stochastic gradient as
    an integer dtype (both RNG plans)."""
    seen = []
    spec = prob_mod.PROBLEMS["logistic"]

    def recording_sgrad(row, theta, key, b_count, b_max):
        seen.append(b_count.dtype)
        return spec.stochastic_grad_row(row, theta, key, b_count, b_max)

    def recording_from_idx(row, theta, idx, b_count):
        seen.append(b_count.dtype)
        return spec.stochastic_grad_from_idx(row, theta, idx, b_count)

    monkeypatch.setitem(
        prob_mod.PROBLEMS, "logistic",
        dataclasses.replace(spec, stochastic_grad_row=recording_sgrad,
                            stochastic_grad_from_idx=recording_from_idx))
    for plan in ("hoisted", "inscan"):
        run_mc(logistic_prob, [_ch()], "gbma", [0.3], 3, 1,
               batch_frac=0.5, rng_plan=plan)
    assert seen and all(np.issubdtype(d, np.integer) for d in seen), seen


# --------------------------------------------------------------------------
# trace-count bookkeeping (satellite)
# --------------------------------------------------------------------------
def test_clear_cache_resets_trace_count(mc):
    run_mc(mc, [_ch()], "gbma", [0.01], 3, 1)
    assert trace_count() >= 1
    cleared = clear_cache()
    assert trace_count() == 0
    run_mc(mc, [_ch()], "gbma", [0.01], 3, 1)
    if cleared:
        assert trace_count() == 1


def test_trace_count_reset_flag(mc):
    clear_cache()
    run_mc(mc, [_ch()], "gbma", [0.01], 3, 1)
    c = trace_count(reset=True)
    if c:  # 0 only if clear_cache is unsupported AND the program cached
        assert c >= 1
    assert trace_count() == 0


# --------------------------------------------------------------------------
# memory model
# --------------------------------------------------------------------------
def test_estimate_peak_bytes_scales_with_chunk():
    base = dict(n_rows=2, seeds=64, steps=100, n_max=256, dim=16,
                algo_set=("gbma",))
    all_live = estimate_peak_bytes(**base)
    chunked = estimate_peak_bytes(**base, seed_chunk=8)
    assert chunked["device_peak_bytes"] < all_live["device_peak_bytes"]
    assert chunked["s_live"] == 8 and all_live["s_live"] == 64
    # chunking bounds the O(S·steps·n_max) terms by the chunk ratio
    assert chunked["rng_draw_bytes"] * 8 == all_live["rng_draw_bytes"]
    blind = estimate_peak_bytes(**{**base, "algo_set": ("blind",)},
                                n_antennas=4)
    assert blind["rng_draw_bytes"] > all_live["rng_draw_bytes"]


def test_estimate_counts_minibatch_index_stream():
    base = dict(n_rows=1, seeds=8, steps=50, n_max=32, dim=8,
                algo_set=("gbma",))
    with_idx = estimate_peak_bytes(**base, b_max=6)
    without = estimate_peak_bytes(**base)
    assert with_idx["rng_draw_bytes"] > without["rng_draw_bytes"]
