"""Monte Carlo engine (`repro.core.montecarlo`) correctness:

  * engine trajectories == reference simulators (`GBMASimulator`, `FDMGD`,
    `PowerControlOTA`, `CentralizedGD`) under a fixed key — the engine
    mirrors their PRNG split order;
  * on-device closed-form excess risk == the numpy objective-difference
    oracle (`benchmarks.common.MSDProblem.excess_risk`);
  * a batched (vmapped) config sweep == the same configs run one at a time;
  * a padded/masked NODE-COUNT sweep compiles `_mc_core` exactly once and
    reproduces the per-N runs; per-row algo batching likewise;
  * `_sample_gains` (the engine's traceable twin) == `channel.sample_gains`
    across all fading families × phase-error settings (property test);
  * energy bookkeeping: `energy_to_target` charges exactly the slots before
    the first target hit (hand-computed regression).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from benchmarks.common import MSDProblem
from repro.core import channel as channel_mod
from repro.core import montecarlo as mc_mod
from repro.core.baselines import CentralizedGD, FDMGD, PowerControlOTA
from repro.core.channel import ChannelConfig
from repro.core.gbma import GBMASimulator, ota_aggregate
from repro.core.montecarlo import (ChannelBatch, MCProblem, MCProblemBatch,
                                   MCResult, energy_to_target,
                                   quadratic_mc_problem, run_mc, trace_count)
from repro.core.theory import stepsize_theorem1

N, STEPS, SEEDS = 40, 60, 2


@pytest.fixture(scope="module")
def prob():
    return MSDProblem.make(N, dim=24)


@pytest.fixture(scope="module")
def mc(prob):
    return prob.to_mc()


def _ch(**kw):
    kw.setdefault("fading", "rayleigh")
    kw.setdefault("noise_std", 0.5)
    return ChannelConfig(**kw)


def test_engine_matches_gbma_simulator_fixed_key(prob, mc):
    ch = _ch()
    beta = stepsize_theorem1(prob.pc, ch, N, safety=0.8)
    res = run_mc(mc, [ch], "gbma", [beta], STEPS, SEEDS)
    for s in range(SEEDS):
        traj = GBMASimulator(prob.grad_fn(), ch, beta).run(
            jnp.zeros(prob.pc.dim), STEPS, jax.random.key(s))
        oracle = prob.excess_risk(traj)
        np.testing.assert_allclose(res.risks[0, s], oracle, rtol=1e-4,
                                   atol=1e-8)


def test_on_device_risk_matches_numpy_oracle(prob, mc):
    """Closed-form 0.5 (θ-θ*)ᵀH(θ-θ*) == objective(θ) - F* (f64 numpy)."""
    thetas = np.random.default_rng(0).standard_normal((8, prob.pc.dim))
    f_star = prob.objective(prob.theta_star)
    for t in thetas:
        dev = float(mc.risk_fn(jnp.asarray(t, jnp.float32)))
        host = prob.objective(t) - f_star
        np.testing.assert_allclose(dev, host, rtol=2e-4)


def test_batched_configs_equal_individual_runs(prob, mc):
    chs = [_ch(energy=e) for e in (1.0, 0.1, 0.01)]
    betas = [stepsize_theorem1(prob.pc, c, N, safety=0.8) for c in chs]
    batched = run_mc(mc, chs, "gbma", betas, STEPS, SEEDS)
    for i, (c, b) in enumerate(zip(chs, betas)):
        single = run_mc(mc, [c], "gbma", [b], STEPS, SEEDS)
        np.testing.assert_allclose(batched.risks[i], single.risks[0],
                                   rtol=1e-5, atol=1e-9)


@pytest.mark.parametrize("algo,invert", [
    ("centralized", False),
    ("fdm", False),
    ("fdm", True),
    ("power_control", False),
])
def test_engine_matches_reference_baselines(prob, mc, algo, invert):
    ch = _ch()
    beta = stepsize_theorem1(prob.pc, ch, N, safety=0.5)
    g = prob.grad_fn()
    if algo == "centralized":
        runner = CentralizedGD(g, beta)
    elif algo == "fdm":
        runner = FDMGD(g, ch, beta, invert_channel=invert)
    else:
        runner = PowerControlOTA(g, ch, beta, h_min=0.3)
    res = run_mc(mc, [ch], algo, [beta], STEPS, 1, invert_channel=invert,
                 h_min=0.3)
    traj = runner.run(jnp.zeros(prob.pc.dim), STEPS, jax.random.key(0))
    np.testing.assert_allclose(res.risks[0, 0], prob.excess_risk(traj),
                               rtol=1e-4, atol=1e-8)


def test_engine_matches_multiantenna_reference(prob, mc):
    """n_antennas=M mirrors `ota_aggregate_multiantenna`'s key splitting
    (including the extra split for M=1)."""
    from repro.core.gbma import ota_aggregate_multiantenna

    ch = _ch()
    beta = stepsize_theorem1(prob.pc, ch, N, safety=0.5)
    g = prob.grad_fn()
    for m_ant in (1, 2):
        res = run_mc(mc, [ch], "gbma", [beta], STEPS, 1, n_antennas=m_ant)

        def body(theta, k):
            v = ota_aggregate_multiantenna(g(theta), k, ch, m_ant)
            return theta - beta * v, theta

        keys = jax.random.split(jax.random.key(0), STEPS)
        theta_fin, traj = jax.lax.scan(body, jnp.zeros(prob.pc.dim), keys)
        traj = jnp.concatenate([traj, theta_fin[None]])
        np.testing.assert_allclose(res.risks[0, 0], prob.excess_risk(traj),
                                   rtol=1e-4, atol=1e-8)


def test_channel_batch_rejects_mixed_fading():
    with pytest.raises(ValueError):
        ChannelBatch.stack([_ch(), _ch(fading="equal")])


def test_energy_accounting_and_target(prob, mc):
    """cum_energy is a per-step cumsum of E_N ||g_k||²; energy_to_target
    charges exactly the slots transmitted before the risk first hits the
    target (a hit at index k has consumed k slots -> cum_energy[k-1])."""
    ch = _ch(fading="equal", noise_std=0.0, energy=0.5)
    beta = stepsize_theorem1(prob.pc, ch, N, safety=0.8)
    res = run_mc(mc, [ch], "gbma", [beta], STEPS, 1)
    cum = res.cum_energy[0, 0]
    assert np.all(np.diff(cum) > 0.0)  # nonzero gradients along the path
    # replicate by hand from the deterministic (noiseless, equal) trajectory
    traj = GBMASimulator(prob.grad_fn(), ch, beta).run(
        jnp.zeros(prob.pc.dim), STEPS, jax.random.key(0))
    g_sq = [float(jnp.sum(prob.grad_fn()(t) ** 2)) for t in traj[:-1]]
    np.testing.assert_allclose(cum, 0.5 * np.cumsum(g_sq), rtol=1e-4)
    target = float(res.risks[0, 0, STEPS // 2])
    tot = energy_to_target(res, target)[0]
    hit = int(np.argmax(res.risks[0, 0] <= target))
    assert hit > 0
    np.testing.assert_allclose(tot, cum[hit - 1], rtol=1e-6)


def _fake_result(risks, cum_energy):
    risks = np.asarray(risks, np.float64)
    mean = risks.mean(axis=1)
    return MCResult(risks=risks, mean=mean, ci95=np.zeros_like(mean),
                    cum_energy=np.asarray(cum_energy, np.float64),
                    bounds=None)


def test_energy_to_target_hand_computed():
    """3-step trajectory, all hit cases: risks [4, 2, 1, .5] with per-slot
    cumulative energy [3, 5, 6]. A first hit at index k costs the first k
    slots; a hit at initialization costs nothing; a never-hit seed spends
    the full horizon."""
    res = _fake_result([[[4.0, 2.0, 1.0, 0.5]]], [[[3.0, 5.0, 6.0]]])
    assert energy_to_target(res, 2.0)[0] == 3.0   # hit at k=1: slot 1 only
    assert energy_to_target(res, 1.0)[0] == 5.0   # hit at k=2: slots 1-2
    assert energy_to_target(res, 0.5)[0] == 6.0   # hit at final k=3
    assert energy_to_target(res, 4.0)[0] == 0.0   # already met at theta_0
    assert energy_to_target(res, 0.1)[0] == 6.0   # never hit: full horizon


def test_nsweep_one_compile_matches_per_n():
    """A node-count sweep (padded/masked to N_max) compiles `_mc_core`
    exactly once and reproduces each per-N run within 1e-5 relative."""
    grid = (12, 19, 32)  # odd size included: exercises the threefry pad
    probs = [MSDProblem.make(n, dim=16) for n in grid]
    chs = [_ch(energy=float(n) ** (-1.0)) for n in grid]
    betas = [stepsize_theorem1(p.pc, c, n, safety=0.8)
             for p, c, n in zip(probs, chs, grid)]
    mcs = [p.to_mc() for p in probs]
    singles = [run_mc(mc, [c], "gbma", [b], STEPS, SEEDS, pc=p.pc)
               for mc, c, b, p in zip(mcs, chs, betas, probs)]
    mc_mod.clear_cache()  # also zeroes the trace counter
    sweep = run_mc(mcs, chs, "gbma", betas, STEPS, SEEDS,
                   pc=[p.pc for p in probs])
    assert trace_count() == 1
    for i, single in enumerate(singles):
        np.testing.assert_allclose(sweep.risks[i], single.risks[0],
                                   rtol=1e-5, atol=1e-9)
        np.testing.assert_allclose(sweep.cum_energy[i],
                                   single.cum_energy[0], rtol=1e-5)
        np.testing.assert_allclose(sweep.bounds[i], single.bounds[0],
                                   rtol=1e-6)


def test_nsweep_localization_problems():
    """The localization problem batches/pads too (far-away pad sensors keep
    the padded rows' 1/d² terms finite)."""
    from repro.core.montecarlo import localization_mc_problem
    from repro.data.synthetic import localization_field

    parts = [localization_field(n, signal_a=100.0, snr_db=-10.0, seed=i)
             for i, n in enumerate((10, 17))]
    locs = [localization_mc_problem(r, x, src, 100.0)
            for r, x, src, _ in parts]
    ch = _ch(noise_std=0.3)
    theta0 = np.array([45.0, 45.0])
    sweep = run_mc(locs, [ch, ch], "gbma", [0.5, 0.5], STEPS, SEEDS,
                   theta0=theta0)
    assert np.all(np.isfinite(sweep.risks))
    for i, loc in enumerate(locs):
        single = run_mc(loc, [ch], "gbma", [0.5], STEPS, SEEDS,
                        theta0=theta0)
        np.testing.assert_allclose(sweep.risks[i], single.risks[0],
                                   rtol=1e-5, atol=1e-9)


def test_algo_batch_one_compile_matches_individual(prob, mc):
    """Per-row algos (the fig4/fig5 shape) run in one `_mc_core` compile
    and match the per-algo runs."""
    ch = _ch()
    beta = stepsize_theorem1(prob.pc, ch, N, safety=0.5)
    algos = ("gbma", "fdm", "centralized")
    mc_mod.clear_cache()  # also zeroes the trace counter
    multi = run_mc(mc, [ch] * 3, algos, [beta] * 3, STEPS, SEEDS)
    assert trace_count() == 1
    for i, a in enumerate(algos):
        single = run_mc(mc, [ch], a, [beta], STEPS, SEEDS)
        np.testing.assert_allclose(multi.risks[i], single.risks[0],
                                   rtol=1e-5, atol=1e-9)


def test_momentum_matches_reference_recursion(prob, mc):
    """algo='momentum'/'nesterov' == a hand-rolled heavy-ball / Nesterov
    loop over the reference OTA slot (`gbma.ota_aggregate`), same keys."""
    ch = _ch()
    beta = 0.5 * stepsize_theorem1(prob.pc, ch, N, safety=0.5)
    gamma = 0.6
    g = prob.grad_fn()
    for algo, nest in (("momentum", 0.0), ("nesterov", 1.0)):
        res = run_mc(mc, [ch], algo, [beta], STEPS, 1, momentum=gamma)

        def body(carry, k):
            theta, m = carry
            g_k = g(theta - nest * beta * gamma * m)
            v = ota_aggregate(g_k, k, ch)
            m = gamma * m + v
            return (theta - beta * m, m), theta

        keys = jax.random.split(jax.random.key(0), STEPS)
        (theta_fin, _), traj = jax.lax.scan(
            body, (jnp.zeros(prob.pc.dim), jnp.zeros(prob.pc.dim)), keys)
        traj = jnp.concatenate([traj, theta_fin[None]])
        np.testing.assert_allclose(res.risks[0, 0], prob.excess_risk(traj),
                                   rtol=1e-4, atol=1e-8)


def test_momentum_zero_gamma_is_vanilla(prob, mc):
    ch = _ch()
    beta = stepsize_theorem1(prob.pc, ch, N, safety=0.8)
    r_mom = run_mc(mc, [ch], "momentum", [beta], STEPS, SEEDS, momentum=0.0)
    r_van = run_mc(mc, [ch], "gbma", [beta], STEPS, SEEDS)
    np.testing.assert_array_equal(r_mom.risks, r_van.risks)


def test_shard_seeds_matches_plain(prob, mc):
    """The shard_map('mc' mesh) seed axis is transparent: forcing it on the
    available devices reproduces the plain path bit-for-bit."""
    ch = _ch()
    beta = stepsize_theorem1(prob.pc, ch, N, safety=0.8)
    plain = run_mc(mc, [ch], "gbma", [beta], STEPS, SEEDS,
                   shard_seeds=False)
    sharded = run_mc(mc, [ch], "gbma", [beta], STEPS, SEEDS,
                     shard_seeds=True)
    np.testing.assert_array_equal(plain.risks, sharded.risks)
    np.testing.assert_array_equal(plain.cum_energy, sharded.cum_energy)


def test_problem_batch_rejects_unstackable():
    handbuilt = MCProblem(grad_fn=lambda t: t[None, :], risk_fn=jnp.sum,
                          dim=3, n_nodes=1)
    with pytest.raises(ValueError):
        MCProblemBatch.stack([handbuilt, handbuilt])
    q = quadratic_mc_problem(np.eye(3, dtype=np.float32),
                             np.zeros(3, np.float32), 0.1, np.zeros(3))
    with pytest.raises(ValueError):
        MCProblemBatch.stack([q, handbuilt])


def test_problem_batch_rejects_mixed_kinds_and_dims():
    from repro.core.montecarlo import localization_mc_problem
    from repro.data.synthetic import localization_field

    q3 = quadratic_mc_problem(np.eye(3, dtype=np.float32),
                              np.zeros(3, np.float32), 0.1, np.zeros(3))
    q4 = quadratic_mc_problem(np.eye(4, dtype=np.float32),
                              np.zeros(4, np.float32), 0.1, np.zeros(4))
    r, x, src, _ = localization_field(5, signal_a=10.0, seed=0)
    loc = localization_mc_problem(r, x, src, 10.0)
    # mixed kinds
    with pytest.raises(ValueError, match="one\\s+kind"):
        MCProblemBatch.stack([q3, loc])
    # same kind, mismatched dims
    with pytest.raises(ValueError, match="dim"):
        MCProblemBatch.stack([q3, q4])
    # unregistered kind
    import dataclasses
    alien = dataclasses.replace(q3, kind="no_such_kind")
    with pytest.raises(ValueError, match="not registered"):
        MCProblemBatch.stack([alien, alien])


def test_localization_pad_sentinel_keeps_padded_gradients_zero():
    """The r=1e6 pad sentinel places padded sensors far from the search
    region so 1/d² stays finite — padded rows must come out EXACTLY zero
    (after masking) and finite (before the mask they must not be inf/nan,
    or 0·inf would poison the row)."""
    from repro.core.mc.problems import (PROBLEMS, localization_mc_problem)
    from repro.data.synthetic import localization_field

    parts = [localization_field(n, signal_a=100.0, snr_db=-10.0, seed=i)
             for i, n in enumerate((4, 9))]
    locs = [localization_mc_problem(r, x, src, 100.0)
            for r, x, src, _ in parts]
    batch = MCProblemBatch.stack(locs)
    assert batch.n_max == 9
    grad_row = PROBLEMS["localization"].grad_row
    theta = jnp.asarray([45.0, 45.0], jnp.float32)
    for i, n in enumerate((4, 9)):
        row = {k: v[i] for k, v in batch.data.items()}
        g = np.asarray(grad_row(row, theta))
        assert np.all(np.isfinite(g))
        assert np.all(g[n:] == 0.0), "padded sensor rows must be exact 0"
        # the pad value itself (not the mask alone) keeps things finite:
        # an unmasked evaluation at the pad sentinel is tiny but finite
        row_nomask = dict(row, mask=jnp.ones_like(row["mask"]))
        g_nomask = np.asarray(grad_row(row_nomask, theta))
        assert np.all(np.isfinite(g_nomask))


def test_quadratic_pad_zero_keeps_padded_gradients_zero():
    from repro.core.mc.problems import PROBLEMS

    probs = [MSDProblem.make(n, dim=6).to_mc() for n in (5, 8)]
    batch = MCProblemBatch.stack(probs)
    grad_row = PROBLEMS["quadratic"].grad_row
    theta = jnp.ones(6, jnp.float32)
    row = {k: v[0] for k, v in batch.data.items()}
    g = np.asarray(grad_row(row, theta))
    assert np.all(g[5:] == 0.0)
    assert np.all(np.isfinite(g))


def test_ota_impl_ref_parity(prob, mc):
    """run_mc(ota_impl='ref') routes the OTA slot through the
    `repro.kernels.ota` jnp oracle; trajectories must match the inline
    path (same RNG stream, same math up to association order)."""
    ch = _ch()
    beta = stepsize_theorem1(prob.pc, ch, N, safety=0.8)
    r_inline = run_mc(mc, [ch], "gbma", [beta], STEPS, SEEDS)
    r_ref = run_mc(mc, [ch], "gbma", [beta], STEPS, SEEDS, ota_impl="ref")
    np.testing.assert_allclose(r_ref.risks, r_inline.risks, rtol=1e-5,
                               atol=1e-9)
    # momentum shares the slot path
    r_mom = run_mc(mc, [ch], "momentum", [beta], STEPS, SEEDS,
                   momentum=0.5)
    r_mom_ref = run_mc(mc, [ch], "momentum", [beta], STEPS, SEEDS,
                       momentum=0.5, ota_impl="ref")
    np.testing.assert_allclose(r_mom_ref.risks, r_mom.risks, rtol=1e-5,
                               atol=1e-9)


def test_ota_impl_pallas_parity_interpret(prob, mc):
    """The Pallas kernel path, run in the Pallas interpreter (asked for
    explicitly: 'pallas' itself is TPU-only), matches inline. Short
    horizon: interpret mode is slow."""
    ch = _ch()
    beta = stepsize_theorem1(prob.pc, ch, N, safety=0.8)
    steps = 5
    r_inline = run_mc(mc, [ch], "gbma", [beta], steps, 1)
    r_pallas = run_mc(mc, [ch], "gbma", [beta], steps, 1,
                      ota_impl="interpret")
    np.testing.assert_allclose(r_pallas.risks, r_inline.risks, rtol=1e-5,
                               atol=1e-9)


def test_ota_impl_rejects_padded_sweeps_and_bad_values(prob, mc):
    probs = [MSDProblem.make(n, dim=8) for n in (6, 9)]
    mcs = [p.to_mc() for p in probs]
    chs = [_ch(), _ch()]
    with pytest.raises(ValueError, match="single node count"):
        run_mc(mcs, chs, "gbma", [0.01, 0.01], 4, 1, ota_impl="ref")
    with pytest.raises(ValueError, match="ota_impl"):
        run_mc(mc, [_ch()], "gbma", [0.01], 4, 1, ota_impl="fast")
    # 'auto' on a padded sweep silently keeps the inline path
    res = run_mc(mcs, chs, "gbma", [0.01, 0.01], 4, 1, ota_impl="auto")
    assert np.all(np.isfinite(res.risks))


@settings(max_examples=24, deadline=None)
@given(fading=st.sampled_from(["equal", "rayleigh", "rician", "lognormal"]),
       scale=st.floats(0.2, 2.0),
       phase=st.sampled_from([0.0, 0.3, 0.78]),
       rician_k=st.floats(0.5, 8.0),
       seed=st.integers(0, 2**16))
def test_sample_gains_twin_matches_reference(fading, scale, phase, rician_k,
                                             seed):
    """The engine's traceable sampler must never drift from the reference
    `channel.sample_gains` (same key -> same draws), across all four fading
    families × phase-error settings."""
    cfg = ChannelConfig(fading=fading, scale=scale, rician_k=rician_k,
                        phase_error_max=phase)
    p = {"scale": jnp.float32(scale), "rician_k": jnp.float32(rician_k),
         "phase_error_max": jnp.float32(phase)}
    key = jax.random.key(seed)
    ref = channel_mod.sample_gains(key, cfg, (23,))
    twin = mc_mod._sample_gains(key, fading, p, (23,))
    np.testing.assert_allclose(np.asarray(twin), np.asarray(ref), rtol=1e-5,
                               atol=1e-7)


@settings(max_examples=16, deadline=None)
@given(fading=st.sampled_from(["equal", "rayleigh", "rician", "lognormal"]),
       n=st.sampled_from([5, 8, 23, 31, 32]),
       seed=st.integers(0, 2**16))
def test_dynamic_n_sampler_matches_shaped_draws(fading, n, seed):
    """`_row_gains` on a padded sweep (the N_max draw, masked) reproduces
    the (n,)-shaped draw in lanes [0, n) — to float rounding — and zeroes
    the rest: prefix-stable threefry draws across every fading family."""
    p = {"scale": jnp.float32(0.9), "rician_k": jnp.float32(4.0),
         "phase_error_max": jnp.float32(0.4), "n_nodes": jnp.float32(n)}
    key = jax.random.key(seed)
    ref = mc_mod._sample_gains(key, fading, p, (n,))
    dyn = mc_mod._row_gains(key, fading, p, (n, 32), 32)
    np.testing.assert_allclose(np.asarray(dyn[:n]), np.asarray(ref),
                               rtol=5e-7, atol=0)
    assert np.all(np.asarray(dyn[n:]) == 0.0)


def test_nsweep_fdm_matches_per_n():
    """fdm node-count sweeps (the per-node noise is a masked N_max draw,
    `_row_normal`) reproduce the per-N runs."""
    probs = [MSDProblem.make(n, dim=12) for n in (10, 17)]
    chs = [_ch() for _ in probs]
    mcs = [p.to_mc() for p in probs]
    for invert in (False, True):
        sweep = run_mc(mcs, chs, "fdm", [0.01, 0.01], STEPS, SEEDS,
                       invert_channel=invert)
        for i, mc in enumerate(mcs):
            single = run_mc(mc, [chs[i]], "fdm", [0.01], STEPS, SEEDS,
                            invert_channel=invert)
            np.testing.assert_allclose(sweep.risks[i], single.risks[0],
                                       rtol=1e-5, atol=1e-9)


@settings(max_examples=10, deadline=None)
@given(n=st.sampled_from([5, 8, 13]), d=st.sampled_from([3, 7]),
       seed=st.integers(0, 2**16))
def test_dynamic_normal_matches_shaped_draws(n, d, seed):
    """`_row_normal` (the fdm per-node noise on a padded sweep) == the
    (n, d)-shaped draw in its first n rows, zero below."""
    key = jax.random.key(seed)
    ref = jax.random.normal(key, (n, d))
    p = {"n_nodes": jnp.float32(n)}
    dyn = mc_mod._row_normal(key, p, (n, 16), 16, d, jnp.float32)
    np.testing.assert_allclose(np.asarray(dyn[:n]), np.asarray(ref),
                               rtol=5e-7, atol=1e-7)
    assert np.all(np.asarray(dyn[n:]) == 0.0)


def test_energy_to_target_vectorizes_over_configs_and_seeds():
    res = _fake_result(
        [[[4.0, 2.0, 1.0, 0.5], [4.0, 3.0, 2.0, 1.0]],
         [[9.0, 8.0, 7.0, 6.0], [0.5, 0.4, 0.3, 0.2]]],
        [[[3.0, 5.0, 6.0], [1.0, 2.0, 10.0]],
         [[1.0, 2.0, 3.0], [4.0, 8.0, 12.0]]])
    out = energy_to_target(res, 2.0)
    # config 0: seed 0 hits at k=1 (3.0), seed 1 at k=2 (2.0) -> mean 2.5
    # config 1: seed 0 never hits (3.0), seed 1 at k=0 (0.0)  -> mean 1.5
    np.testing.assert_allclose(out, [2.5, 1.5])
