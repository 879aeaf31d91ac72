"""Blind-transmitter family (`algo="blind"/"blind_ec"`) correctness:

  * engine trajectories == the reference `gbma.blind_ota_aggregate` scan
    under a fixed key (same split order), including the energy account;
  * complex-gain samplers: the engine's traceable twin ==
    `channel.sample_complex_gains` across fading families (property test),
    and the dynamic-count twin == the shaped draws;
  * per-row antenna counts: the counts-as-data key split replays
    `jax.random.split(key, m)` exactly, an M-sweep batches in ONE
    `_mc_core` compile and matches the static per-M runs; node-count
    sweeps likewise;
  * degenerate cases: a large-M blind slot approaches the equal-gain GBMA
    (= mean-gradient) update at the documented O(sqrt(N/(M m2)))
    tolerance; `blind_ec` with a non-binding budget is bit-identical to
    `blind`; with zero noise and many antennas both converge like
    centralized GD and agree at the horizon;
  * a hand-computed single-step value (equal-gain family, M=1) pins the
    MRC combiner formula and its RNG discipline;
  * `blind_ec` budget: per-slot transmitted energy never exceeds E_N·N·B.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from benchmarks.common import MSDProblem
from repro.core import channel as channel_mod
from repro.core import montecarlo as mc_mod
from repro.core.channel import ChannelConfig
from repro.core.gbma import blind_ota_aggregate
from repro.core.montecarlo import run_mc, trace_count

N, STEPS, SEEDS = 24, 40, 2


@pytest.fixture(scope="module")
def prob():
    return MSDProblem.make(N, dim=16)


@pytest.fixture(scope="module")
def mc(prob):
    return prob.to_mc()


def _ch(**kw):
    kw.setdefault("fading", "rayleigh")
    kw.setdefault("noise_std", 0.5)
    return ChannelConfig(**kw)


def test_engine_matches_blind_reference(prob, mc):
    """Engine algo='blind' == a hand scan over `blind_ota_aggregate` with
    the same keys; cum_energy == E_N Σ‖g_n‖² along that trajectory."""
    ch = _ch(energy=0.25)
    beta = 0.02
    g = prob.grad_fn()
    for m_ant in (1, 3):
        res = run_mc(mc, [ch], "blind", [beta], STEPS, 1, n_antennas=m_ant)

        def body(theta, k):
            v = blind_ota_aggregate(g(theta), k, ch, m_ant)
            return theta - beta * v, theta

        keys = jax.random.split(jax.random.key(0), STEPS)
        theta_fin, traj = jax.lax.scan(body, jnp.zeros(prob.pc.dim), keys)
        traj = jnp.concatenate([traj, theta_fin[None]])
        np.testing.assert_allclose(res.risks[0, 0], prob.excess_risk(traj),
                                   rtol=1e-4, atol=1e-8)
        g_sq = [float(jnp.sum(g(t) ** 2)) for t in traj[:-1]]
        np.testing.assert_allclose(res.cum_energy[0, 0],
                                   ch.energy * np.cumsum(g_sq), rtol=1e-4)


def test_blind_large_m_approaches_equal_gain_update():
    """Degeneracy (documented in docs/algorithms.md): with many antennas
    the blind MRC combine concentrates on the equal-gain GBMA update — the
    plain mean gradient at zero noise. Deviation is O(sqrt(N/(M m2)));
    at N=8, M=16384 the relative L2 error is ~2% (1.2-2.3% over seeds
    0-7; at M=4096 it is ~4% and single seeds exceed 6%), asserted at
    the documented 5% tolerance."""
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.standard_normal((8, 12)), jnp.float32)
    ch = _ch(noise_std=0.0)
    for seed in (0, 1):
        v = np.asarray(blind_ota_aggregate(g, jax.random.key(seed), ch,
                                           16384))
        vc = np.asarray(jnp.mean(g, axis=0))
        rel = np.linalg.norm(v - vc) / np.linalg.norm(vc)
        assert rel < 0.05, f"seed {seed}: rel L2 {rel:.3f} >= 5%"


def test_blind_single_step_hand_computed():
    """Equal-gain family, M=1, N=2: recompute the MRC combine by hand from
    the raw draws — pins both the formula v = (A y_r + B y_i)/(N m2) and
    the key-split discipline (slot -> antenna -> (k_h, k_w) -> (mag, ph))."""
    scale, noise_std, energy = 1.3, 0.7, 0.25
    cfg = ChannelConfig(fading="equal", scale=scale, noise_std=noise_std,
                        energy=energy)
    g = np.asarray([[1.0, -2.0, 0.5], [0.25, 3.0, -1.0]], np.float32)
    key = jax.random.key(11)
    v = np.asarray(blind_ota_aggregate(jnp.asarray(g), key, cfg, 1))
    # replay the draws with the documented split order
    (k_ant,) = jax.random.split(key, 1)
    k_h, k_w = jax.random.split(k_ant)
    _, k_ph = jax.random.split(k_h)  # k_mag unused for the 'equal' family
    phi = np.asarray(jax.random.uniform(k_ph, (2,), minval=-np.pi,
                                        maxval=np.pi))
    z = np.asarray(jax.random.normal(k_w, (2, 3)))
    a, b = scale * np.cos(phi), scale * np.sin(phi)
    std = noise_std / np.sqrt(energy)
    y_r = a @ g + std * z[0]
    y_i = b @ g + std * z[1]
    expect = (a.sum() * y_r + b.sum() * y_i) / (1 * 2 * scale**2)
    np.testing.assert_allclose(v, expect, rtol=1e-5, atol=1e-7)


def test_blind_ec_non_binding_budget_is_bit_identical(prob, mc):
    """With the default unbounded budget nothing is ever truncated: the
    residual stays 0 and blind_ec == blind bit-for-bit."""
    ch = _ch()
    r_ec = run_mc(mc, [ch], "blind_ec", [0.02], STEPS, SEEDS, n_antennas=3)
    r_bl = run_mc(mc, [ch], "blind", [0.02], STEPS, SEEDS, n_antennas=3)
    np.testing.assert_array_equal(r_ec.risks, r_bl.risks)
    np.testing.assert_array_equal(r_ec.cum_energy, r_bl.cum_energy)


def test_blind_ec_zero_noise_large_m_matches_blind(prob, mc):
    """Zero noise + many antennas: the channel is effectively perfect, the
    budget binds only while gradients are large, and the residual
    re-injects exactly what was cut — blind_ec converges to the same
    optimum as blind (== centralized here), tracking its trajectory with
    a bounded delay (the truncation shifts, not breaks, the exponential
    tail)."""
    ch = _ch(noise_std=0.0)
    g0 = np.asarray(mc.grad_fn(jnp.zeros(prob.pc.dim, jnp.float32)))
    budget = 0.5 * float(np.mean(np.sum(g0**2, axis=1)))
    steps = 150
    r_bl = run_mc(mc, [ch], "blind", [0.02], steps, 1, n_antennas=256)
    r_ec = run_mc(mc, [ch], "blind_ec", [0.02], steps, 1, n_antennas=256,
                  power_budget=budget)
    init = r_bl.risks[0, 0, 0]
    assert r_bl.risks[0, 0, -1] < 1e-2 * init
    assert r_ec.risks[0, 0, -1] < 1e-2 * init
    # ec's horizon risk is within blind's trajectory a bounded number of
    # steps earlier (observed delay ≈ 42 slots at this budget; bound 60)
    assert r_ec.risks[0, 0, -1] <= r_bl.risks[0, 0, steps - 60]


def test_blind_ec_budget_caps_slot_energy(prob, mc):
    """Per-slot transmitted energy is at most E_N · N · B when the budget
    binds (each node transmits at most B in squared norm)."""
    ch = _ch(energy=0.5)
    budget = 1e-3
    res = run_mc(mc, [ch], "blind_ec", [0.05], STEPS, 1, n_antennas=8,
                 power_budget=budget)
    inc = np.diff(np.concatenate(
        [np.zeros((1,)), res.cum_energy[0, 0]]))
    cap = ch.energy * N * budget
    assert np.all(inc <= cap * (1.0 + 1e-4))  # f32 cumsum rounding slack
    assert inc.max() > 0.5 * cap  # the budget actually binds here


def test_ec_flag_select_does_not_leak_nan_into_other_rows():
    """A non-ec row whose per-node squared norm overflows f32 (sq = inf)
    while its budget is the default inf makes the (unused) α expression
    inf/inf = NaN; the per-row select must keep that row on the exact
    x = g path instead of NaN-poisoning its trajectory from step one."""
    from repro.core.montecarlo import MCProblem

    big = 1.0e19  # Σ_d big² overflows f32; g and the trajectory stay finite
    n, d = 4, 8
    problem = MCProblem(
        grad_fn=lambda theta: jnp.full((n, d), big) + 0.0 * theta[None, :],
        risk_fn=lambda theta: jnp.sum(theta**2),
        dim=d, n_nodes=n)
    ch = _ch(fading="equal", noise_std=0.0)
    res = run_mc(problem, [ch, ch], ("gbma", "blind_ec"), [1e-18, 1e-18],
                 8, 1, n_antennas=(1, 2), power_budget=[np.inf, 1.0])
    assert not np.any(np.isnan(res.risks))
    # the gbma row really stepped on the huge gradients (θ_k = -β·big·k)
    np.testing.assert_allclose(res.risks[0, 0, 1], d * 10.0**2, rtol=1e-4)


def test_blind_msweep_one_compile_matches_static(prob, mc):
    """Per-row antenna counts (the fig7b shape) run in ONE `_mc_core`
    compile and match the static per-M runs."""
    ch = _ch()
    ms = (1, 3, 8)
    mc_mod.clear_cache()  # also zeroes the trace counter
    multi = run_mc(mc, [ch] * 3, "blind", [0.02] * 3, STEPS, SEEDS,
                   n_antennas=ms)
    assert trace_count() == 1
    for i, m in enumerate(ms):
        single = run_mc(mc, [ch], "blind", [0.02], STEPS, SEEDS,
                        n_antennas=m)
        np.testing.assert_allclose(multi.risks[i], single.risks[0],
                                   rtol=1e-5, atol=1e-9)


def test_gbma_per_row_antennas_match_static_mrc(prob, mc):
    """The per-row antenna axis also covers the gbma MRC path."""
    ch = _ch()
    multi = run_mc(mc, [ch] * 2, "gbma", [0.02] * 2, STEPS, SEEDS,
                   n_antennas=(2, 4))
    for i, m in enumerate((2, 4)):
        single = run_mc(mc, [ch], "gbma", [0.02], STEPS, SEEDS,
                        n_antennas=m)
        np.testing.assert_allclose(multi.risks[i], single.risks[0],
                                   rtol=1e-5, atol=1e-9)


def test_blind_nsweep_one_compile_matches_per_n():
    """A blind node-count sweep (padded N axis + per-antenna complex
    draws) compiles once and reproduces the per-N runs."""
    grid = (9, 14)
    probs = [MSDProblem.make(n, dim=8) for n in grid]
    mcs = [p.to_mc() for p in probs]
    ch = _ch()
    mc_mod.clear_cache()  # also zeroes the trace counter
    sweep = run_mc(mcs, [ch, ch], "blind", [0.02] * 2, STEPS, SEEDS,
                   n_antennas=4)
    assert trace_count() == 1
    for i, m in enumerate(mcs):
        single = run_mc(m, [ch], "blind", [0.02], STEPS, SEEDS,
                        n_antennas=4)
        np.testing.assert_allclose(sweep.risks[i], single.risks[0],
                                   rtol=1e-5, atol=1e-9)


def test_blind_requires_antennas(mc):
    with pytest.raises(ValueError):
        run_mc(mc, [_ch()], "blind", [0.02], 4, 1)


@settings(max_examples=16, deadline=None)
@given(fading=st.sampled_from(["equal", "rayleigh", "rician", "lognormal"]),
       scale=st.floats(0.2, 2.0),
       rician_k=st.floats(0.5, 8.0),
       seed=st.integers(0, 2**16))
def test_complex_sampler_twin_matches_reference(fading, scale, rician_k,
                                                seed):
    """The engine's traceable complex sampler must never drift from the
    reference `channel.sample_complex_gains` (same key -> same draws)."""
    cfg = ChannelConfig(fading=fading, scale=scale, rician_k=rician_k)
    p = {"scale": jnp.float32(scale), "rician_k": jnp.float32(rician_k)}
    key = jax.random.key(seed)
    ra, rb = channel_mod.sample_complex_gains(key, cfg, (17,))
    ta, tb = mc_mod._sample_complex_gains(key, fading, p, (17,))
    np.testing.assert_allclose(np.asarray(ta), np.asarray(ra), rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(tb), np.asarray(rb), rtol=1e-5,
                               atol=1e-7)


@settings(max_examples=12, deadline=None)
@given(fading=st.sampled_from(["equal", "rayleigh", "rician", "lognormal"]),
       n=st.sampled_from([5, 8, 23, 32]),
       seed=st.integers(0, 2**16))
def test_dynamic_complex_sampler_matches_shaped_draws(fading, n, seed):
    """`_row_complex_gains` on a padded sweep (the N_max draw, masked) ==
    the (n,)-shaped draw in lanes [0, n), zero elsewhere — the blind
    family's N-sweep draw."""
    p = {"scale": jnp.float32(0.9), "rician_k": jnp.float32(4.0),
         "n_nodes": jnp.float32(n)}
    key = jax.random.key(seed)
    ra, rb = mc_mod._sample_complex_gains(key, fading, p, (n,))
    da, db = mc_mod._row_complex_gains(key, fading, p, (n, 32), 32)
    for ref, dyn in ((ra, da), (rb, db)):
        # rounding (fma association) differences only; atol covers the
        # sin(phi)-near-zero lanes where rtol alone is meaningless
        np.testing.assert_allclose(np.asarray(dyn[:n]), np.asarray(ref),
                                   rtol=5e-7, atol=5e-7)
        assert np.all(np.asarray(dyn[n:]) == 0.0)


@settings(max_examples=10, deadline=None)
@given(m=st.sampled_from([1, 2, 5, 8]), seed=st.integers(0, 2**16))
def test_antenna_key_replay_matches_split(m, seed):
    """`_antenna_keys` (a split at the sweep's M_max) == `split(key, m)`
    in the first m lanes (the per-row M-sweep RNG discipline)."""
    key = jax.random.key(seed)
    keys = mc_mod._antenna_keys(key, (1, 8))
    ref = jax.random.key_data(jax.random.split(key, m))
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(keys))[:m], np.asarray(ref))
