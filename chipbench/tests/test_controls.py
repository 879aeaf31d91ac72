"""`correct` separates sound runs from broken ones, at a size a CPU test
run holds, with each cell's own limits (`traffic/*.json`):

* the program passes every compared number, and the control (the plain
  reference one precision step lower, in the program's place) stands ten
  times or more apart from it on one of them;
* a whole run, the look for a chip skipped, comes out correct, and comes
  out not correct once for each fault the cell can have, planted in the
  program underneath the timed path.
"""
import numpy as np
import pytest

from chipbench.tests import tiny

SWEEPS = ["msd_rayleigh.energy", "msd_rayleigh.nsweep"]
TRAINING = ["repro100m_ota.gbma"]
CELLS = [w for w in SWEEPS + TRAINING
         if w in {c["name"] for c in tiny.spec()["workloads"]}]


def _workload(tmp_path, workload, seed=11):
    import jax

    cat = tiny.catalog(str(tmp_path), [workload])
    cell = cat.cell(workload)
    work = cat.module("generators", cell["traffic"]["generator"]).Workload(
        cell["config"], cell["traffic"], seed, jax.devices()[:1], cat)
    return work, cell["traffic"]["limits"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_stands_apart_from_the_program(tmp_path, workload):
    """The program passes every limit, and the control's gap is ten times
    the program's or more on some compared number. The control's gaps grow
    with the cell's size: it fails a limit at the cell's own size on the
    chip (readings in PERF.md), which a test run cannot hold."""
    work, limits = _workload(tmp_path, workload)
    work.setup()
    if workload in SWEEPS:
        work.call(0)
    out = work.calibrate()
    for name, limit in limits.items():
        assert out["program"][name] <= limit, (name, out["program"])
    assert any(out["control"][name] >= 10 * out["program"][name]
               for name in limits), out


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tmp_path, workload):
    res = tiny.run(tiny.catalog(str(tmp_path), [workload]), workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1


# ---------------------------------------------------------------- sweeps
def _rewrite(res, fn):
    """The sweep's per-seed answers altered by `fn`, its statistics taken
    again from them as the program takes them."""
    import dataclasses

    from repro.core.mc.exec import host_seed_stats

    risks, cum = fn(res.risks.copy(), res.cum_energy.copy())
    mean, ci95 = host_seed_stats(risks)
    return dataclasses.replace(res, risks=risks, cum_energy=cum,
                               mean=mean.astype(np.float32),
                               ci95=ci95.astype(np.float32))


def _frozen(monkeypatch):
    """The compiled sweep steps with a zero stepsize: every step returns
    the iterate it was given."""
    import repro.core.mc.engine as engine

    core = engine._mc_core

    def frozen_core(params, betas, *a, **k):
        return core(params, betas * 0.0, *a, **k)
    monkeypatch.setattr(engine, "_mc_core", frozen_core)


def _patch_results(monkeypatch, fn):
    import repro.core.mc as mc

    run_mc = mc.run_mc
    monkeypatch.setattr(mc, "run_mc",
                        lambda *a, **k: _rewrite(run_mc(*a, **k), fn))


def _half(monkeypatch):
    """Half of the seeds left out, their places and the mean taken by the
    rest."""
    def keep_half(r, c):
        s = r.shape[1] // 2
        r[:, s:], c[:, s:] = r[:, :s], c[:, :s]
        return r, c
    _patch_results(monkeypatch, keep_half)


def _altered(monkeypatch):
    """One trajectory's answer replaced by another's."""
    def swap(r, c):
        r[:, 0], c[:, 0] = r[:, 1], c[:, 1]
        return r, c
    _patch_results(monkeypatch, swap)


SWEEP_FAULTS = {"state_unchanged": _frozen, "half_the_seeds": _half,
                "answer_altered": _altered}


@pytest.mark.parametrize("fault", sorted(SWEEP_FAULTS))
@pytest.mark.parametrize("workload", [w for w in CELLS if w in SWEEPS])
def test_sweep_fault_is_not_correct(tmp_path, monkeypatch, workload, fault):
    SWEEP_FAULTS[fault](monkeypatch)
    res = tiny.run(tiny.catalog(str(tmp_path), [workload]), workload)
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1


# ---------------------------------------------------------------- training
def _train_fault(kind):
    def wrap(build):
        def build_broken(model, tcfg, opt):
            real = build(model, tcfg, opt)

            def step(params, opt_state, batch, i):
                tokens = batch["tokens"]
                if kind == "half_the_batch":
                    n = tcfg.gbma.n_nodes
                    per = tokens.shape[0] // n
                    keep = np.concatenate([np.arange(j * per,
                                                     j * per + per // 2)
                                           for j in range(n)])
                    tokens = tokens[np.repeat(keep, 2)]
                if kind == "token_altered":
                    tokens = tokens.at[0, 1].add(1)
                new, state, metrics = real(params, opt_state,
                                           {"tokens": tokens}, i)
                if kind == "state_unchanged":
                    new = params
                return new, state, metrics

            step.init_state = real.init_state
            return step
        return build_broken
    return wrap


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch",
                                   "token_altered"])
@pytest.mark.parametrize("workload", [w for w in CELLS if w in TRAINING])
def test_training_fault_is_not_correct(tmp_path, monkeypatch, workload,
                                       fault):
    import repro.training.train_step as ts

    monkeypatch.setattr(ts, "build_train_step",
                        _train_fault(fault)(ts.build_train_step))
    res = tiny.run(tiny.catalog(str(tmp_path), [workload]), workload)
    assert not res["correct"], res["checks"]
