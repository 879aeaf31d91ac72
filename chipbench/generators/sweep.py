"""Monte Carlo sweep cells: every call in the window is one
`repro.core.mc.run_mc` sweep, ending in the host-side curves a researcher
plots (and, where the traffic asks, the energy-to-target accounting of
paper Fig. 6).

Traffic parameters (`traffic/<config>.<traffic>.json`):

  rows          {"n_nodes": [...], "energy_eps": [...]}: one sweep row per
                entry, E_N = N^(eps - 2). Rows that share one node count
                share one problem; distinct counts pad into one call.
  steps, seeds  per row, per sweep
  run_mc        keyword arguments of the call (the execution plan)
  bounds        pass the Theorem-1 constants, as the paper's figures do
  energy_target run `energy_to_target` on each result at this risk
  check_sweeps  how many of the window's sweeps the reference replays
  ref_block     seeds per block of the reference
  limits        the limit of each compared number

The data set is the configuration's (`problem.data_seed`), as the paper's
is one fixed data set; `--seed` draws the Monte Carlo seeds. Every sweep
of a run has the same shapes and its own seeds: sweep i starts at seed
`seed0 + (i + 1) * seeds`, so the window does a fixed amount of work.
"""
from __future__ import annotations

import numpy as np

from chipbench import counting, gen

SEED_SPACE = 2**30  # run_mc's seed ints are int32


class Workload:
    span_names = ("sweep.call", "sweep.post")

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 catalog):
        self.cfg, self.tr = config, traffic
        self.devices = devices
        self.ref = catalog.module("reference", config["reference"])
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Draw this run's Monte Carlo seeds and check sample from `seed`."""
        ss = np.random.SeedSequence(seed)
        self.seed0 = int(ss.generate_state(1)[0]) % SEED_SPACE
        self.check_rng = np.random.default_rng(ss.spawn(1)[0])
        self.results = []

    def start(self) -> None:
        self.results = []

    # ------------------------------------------------------------ set-up
    def inputs(self) -> dict:
        """The traffic of this run: each node count's data set and optimum,
        each row's energy and stepsize, and the first sweep's seed."""
        cfg, tr = self.cfg, self.tr
        pcfg, ch = cfg["problem"], cfg["channel"]
        ns = list(tr["rows"]["n_nodes"])
        self.probs = {n: gen.msd_problem(
            n, pcfg["dim"], pcfg["lambda"], pcfg["delta"],
            pcfg["data_seed"], pcfg["target_noise_std"])
            for n in dict.fromkeys(ns)}
        self.rows = []
        for n, eps in zip(ns, tr["rows"]["energy_eps"]):
            rule = cfg["stepsize"]
            beta = (gen.theorem1_stepsize(self.probs[n], ch["scale"], n,
                                          rule["safety"])
                    if rule["rule"] == "theorem1" else rule["beta"])
            self.rows.append({"n": n, "energy": float(n) ** (eps - 2.0),
                              "beta": beta})
        return {"seed0": self.seed0, "rows": self.rows,
                **{f"{k}.{n}": p[k] for n, p in self.probs.items()
                   for k in ("X", "y", "theta_star")}}

    def setup(self) -> None:
        import jax

        from repro.core.channel import ChannelConfig
        from repro.core.mc import energy_to_target, quadratic_mc_problem, \
            run_mc
        from repro.core.theory import ProblemConstants

        self._run_mc, self._e2t = run_mc, energy_to_target
        self._annotate = jax.profiler.TraceAnnotation
        cfg, tr = self.cfg, self.tr
        ch = cfg["channel"]
        ns = list(tr["rows"]["n_nodes"])
        self.inputs()
        mc = {n: quadratic_mc_problem(
            p["X"], p["y"], p["lam"], p["theta_star"])
            for n, p in self.probs.items()}
        problem = mc[ns[0]] if len(mc) == 1 else [mc[n] for n in ns]
        chans = [ChannelConfig(fading=ch["fading"], scale=ch["scale"],
                               noise_std=ch["noise_std"], energy=r["energy"])
                 for r in self.rows]
        kwargs = dict(tr.get("run_mc", {}))
        if tr.get("bounds"):
            kwargs["pc"] = [ProblemConstants(
                mu=p["mu"], L=p["L"], L_bar=p["L_bar"], delta=p["delta"],
                r0_sq=p["r0_sq"], dim=p["dim"])
                for p in (self.probs[n] for n in ns)]
        self._args = (problem, chans, cfg["algo"],
                      [r["beta"] for r in self.rows], tr["steps"],
                      tr["seeds"])
        self._kwargs = kwargs
        with jax.default_device(self.devices[0]):
            self._sweep(self.seed0)  # the warm call: every shape compiles
        self.results.clear()

    def _sweep(self, seed0: int):
        with self._annotate("sweep.call"):
            res = self._run_mc(*self._args, seed0=seed0, **self._kwargs)
        e2t = None
        if "energy_target" in self.tr:
            with self._annotate("sweep.post"):
                e2t = self._e2t(res, self.tr["energy_target"])
        return res, e2t

    # ------------------------------------------------------------ window
    def call(self, i: int) -> None:
        import jax

        seed0 = self.seed0 + (i + 1) * self.tr["seeds"]
        with jax.default_device(self.devices[0]):
            res, e2t = self._sweep(seed0)
        self.results.append((seed0, res, e2t))

    def end_to_end(self, calls: int, elapsed: float) -> dict:
        """Seconds per whole sweep, under the name of each sweep metric (a
        cell reports the one BENCHMARK.json gives it)."""
        per_sweep = elapsed / calls
        return {"sweep_s": per_sweep, "padded_sweep_s": per_sweep}

    def counts(self, calls: int, elapsed: float) -> dict:
        tr = self.tr
        # one slot per step for every trajectory, at each row's own N
        per_row = [counting.ota_slot(tr["seeds"], r["n"],
                                     self.cfg["problem"]["dim"])
                   for r in self.rows]
        slot = {k: sum(w[k] for w in per_row) for k in per_row[0]}
        return {"ota_slot": {"work": slot, "calls": calls * tr["steps"]}}

    # ------------------------------------------------------------ check
    def release(self) -> None:
        import gc

        self._args = self._kwargs = None
        gc.collect()

    def check(self) -> tuple:
        """Replay a sample of the window's sweeps (the last one and others
        drawn from the seed) through the reference; returns the compared
        numbers and how many sweeps failed."""
        tr = self.tr
        n = len(self.results)
        picks = {n - 1}
        picks |= set(self.check_rng.choice(
            n, size=min(n, tr["check_sweeps"]) - 1, replace=False).tolist()) \
            if n > 1 and tr["check_sweeps"] > 1 else set()
        worst, failed = {}, 0
        for i in sorted(picks):
            seed0, res, e2t = self.results[i]
            gaps = self.compare(seed0, res, e2t)
            if any(gaps[k] > tr["limits"][k] for k in gaps):
                failed += 1
            for k, v in gaps.items():
                worst[k] = max(worst.get(k, 0.0), v)
        checks = [{"name": k, "value": worst[k], "limit": tr["limits"][k]}
                  for k in tr["limits"]]
        return checks, failed

    def reference_sweep(self, seed0: int, precision: str,
                        frozen: bool = False) -> dict:
        ch, tr = self.cfg["channel"], self.tr
        seed_ints = np.arange(seed0, seed0 + tr["seeds"], dtype=np.int64)
        risks, spent = [], []
        for r in self.rows:
            rr, ee = self.ref.row(
                self.probs[r["n"]], beta=0.0 if frozen else r["beta"],
                energy=r["energy"],
                noise_std=ch["noise_std"], scale=ch["scale"],
                steps=tr["steps"], seed_ints=seed_ints,
                block=tr["ref_block"], precision=precision)
            risks.append(rr)
            spent.append(ee)
        risks, spent = np.stack(risks), np.stack(spent)
        mean, ci95 = self.ref.seed_stats(risks)
        out = {"risks": risks, "cum_energy": spent, "mean": mean,
               "ci95": ci95}
        if "energy_target" in tr:
            out["e2t"] = self.ref.energy_to_target(risks, spent,
                                                   tr["energy_target"])
        return out

    @staticmethod
    def program_answer(res, e2t) -> dict:
        out = {"risks": res.risks, "cum_energy": res.cum_energy,
               "mean": res.mean, "ci95": res.ci95}
        if e2t is not None:
            out["e2t"] = e2t
        return out

    def gaps(self, answer: dict, ref: dict) -> dict:
        """The widest relative gaps between an answer and the reference."""
        def rel(a, b):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            return float(np.max(np.abs(a - b) / np.abs(b)))

        out = {}
        if answer["risks"] is not None:
            out["risk_gap"] = rel(answer["risks"], ref["risks"])
            out["energy_gap"] = rel(answer["cum_energy"], ref["cum_energy"])
        out["mean_gap"] = rel(answer["mean"], ref["mean"])
        # every seed starts from the same iterate: no spread at k = 0
        out["ci95_gap"] = rel(answer["ci95"][..., 1:], ref["ci95"][..., 1:])
        if "e2t" in answer:
            out["e2t_gap"] = rel(answer["e2t"], ref["e2t"])
        return out

    def compare(self, seed0: int, res, e2t) -> dict:
        """The compared gaps of one window sweep against the reference's
        replay of it."""
        gaps = self.gaps(self.program_answer(res, e2t),
                         self.reference_sweep(seed0, "highest"))
        return {k: v for k, v in gaps.items() if k in self.tr["limits"]}

    # ------------------------------------------------------------ calibration
    def calibrate(self) -> dict:
        """Readings of the last window sweep, of the control (the reference
        at three bfloat16 passes in the program's place) and of the faults a
        sweep can have, planted in the reference put in the program's
        place: a step that leaves the state unchanged, half of the seeds
        left out with the mean over the rest, one trajectory's answer
        replaced by another's."""
        seed0, res, e2t = self.results[-1]
        ref = self.reference_sweep(seed0, "highest")
        out = {"program": self.gaps(self.program_answer(res, e2t), ref),
               "control": self.gaps(self.reference_sweep(seed0, "bf16x3"),
                                    ref)}
        mean_only = res.risks is None
        frozen = self.reference_sweep(seed0, "highest", frozen=True)
        faults = {"frozen": frozen}
        s = self.tr["seeds"]
        half = {k: np.array(v) for k, v in ref.items()}
        half["risks"][:, s // 2:] = half["risks"][:, :s // 2]
        half["cum_energy"][:, s // 2:] = half["cum_energy"][:, :s // 2]
        faults["half"] = self._restat(half)
        swapped = {k: np.array(v) for k, v in ref.items()}
        swapped["risks"][:, 0] = swapped["risks"][:, 1]
        swapped["cum_energy"][:, 0] = swapped["cum_energy"][:, 1]
        faults["swapped"] = self._restat(swapped)
        out["faults"] = {}
        for name, ans in faults.items():
            if mean_only:
                ans = dict(ans, risks=None)
            out["faults"][name] = self.gaps(ans, ref)
        return out

    def _restat(self, ans: dict) -> dict:
        ans["mean"], ans["ci95"] = self.ref.seed_stats(ans["risks"])
        if "energy_target" in self.tr:
            ans["e2t"] = self.ref.energy_to_target(
                ans["risks"], ans["cum_energy"], self.tr["energy_target"])
        return ans
