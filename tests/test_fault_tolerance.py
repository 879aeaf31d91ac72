"""Fault-tolerant sweep execution (ISSUE 10 tentpole):

  * crash-safe checkpoints: atomic writes with a content sha256 and
    keep-last-2 rotation; truncated / zero-length / bit-flipped
    artifacts raise the typed `CheckpointCorrupt` instead of raw
    unpickling errors, and the resume path falls back to the last good
    checkpoint;
  * `RetryPolicy`: capped exponential backoff with an injectable sleep;
  * chunk-level fault isolation in `run_chunked`: a sweep surviving k
    injected chunk faults (within the retry budget) is BIT-identical to
    the fault-free run — the headline invariant, plus a property test
    over random fault schedules;
  * node dropout (`run_mc(participation=)`): p = 1.0 statically
    disables the mask stream and is bit-identical to today; p < 1 is
    one extra hoisted stream and a per-row p sweep is one compile.

Serving-level fault tolerance (deadlines, quarantine, server retry)
lives in tests/test_serving_mc.py next to the rest of the server suite.
"""
import os
import warnings

import numpy as np
import pytest

from _fault_harness import ChunkFaultSchedule, bit_flip, torn_write
from _hypothesis_compat import given, settings, strategies as st
from benchmarks.common import MSDProblem
from repro.checkpoint import ckpt
from repro.checkpoint.ckpt import CheckpointCorrupt
from repro.core.channel import ChannelConfig
from repro.core.mc import ExecPlan, RetryPolicy, validate_plan
from repro.core.mc import exec as exec_mod
from repro.core.montecarlo import run_mc

N, D, STEPS, SEEDS = 10, 6, 8, 8


@pytest.fixture(scope="module")
def mc():
    return MSDProblem.make(N, dim=D).to_mc()


def _ch(**kw):
    kw.setdefault("noise_std", 0.5)
    return ChannelConfig(**kw)


def _tree():
    return {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "n": np.int64(5)}


# --------------------------------------------------------------------------
# crash-safe checkpoints
# --------------------------------------------------------------------------
class TestCheckpointCorruption:
    def test_roundtrip_carries_and_strips_the_sha(self, tmp_path):
        path = str(tmp_path / "c.npz")
        ckpt.save(path, _tree())
        raw = ckpt.peek(path)
        assert set(raw) == {"a", "n"}  # the sha leaf never leaks out
        np.testing.assert_array_equal(raw["a"], _tree()["a"])
        with np.load(path) as f:  # but it IS in the artifact
            assert "__sha256__" in f and f["__sha256__"].shape == (32,)

    def test_zero_length_file_raises_typed_corrupt(self, tmp_path):
        path = str(tmp_path / "c.npz")
        open(path, "wb").close()
        with pytest.raises(CheckpointCorrupt) as ei:
            ckpt.peek(path)
        assert ei.value.path == path
        assert "zero-length" in ei.value.reason

    def test_torn_write_raises_typed_corrupt(self, tmp_path):
        path = str(tmp_path / "c.npz")
        ckpt.save(path, _tree())
        torn_write(path)
        with pytest.raises(CheckpointCorrupt) as ei:
            ckpt.peek(path)
        assert ei.value.path == path
        with pytest.raises(CheckpointCorrupt):
            ckpt.restore(path, _tree())

    def test_bit_flip_in_payload_raises_typed_corrupt(self, tmp_path):
        # a raw on-disk flip trips the archive's CRC first — still the
        # typed error, never a raw zipfile/numpy exception
        path = str(tmp_path / "c.npz")
        tree = _tree()
        ckpt.save(path, tree)
        bit_flip(path, needle=tree["a"].tobytes())
        with pytest.raises(CheckpointCorrupt) as ei:
            ckpt.peek(path)
        assert ei.value.path == path

    def test_silent_payload_tamper_raises_sha_mismatch(self, tmp_path):
        # CRC-consistent tampering (archive rewritten with one value
        # changed but the stale sha leaf kept) only the content sha sees
        path = str(tmp_path / "c.npz")
        ckpt.save(path, _tree())
        with np.load(path) as f:
            flat = {k: f[k].copy() for k in f.files}
        flat["a"].flat[0] += 1.0
        with open(path, "wb") as f:
            np.savez(f, **flat)
        with pytest.raises(CheckpointCorrupt, match="sha256 mismatch"):
            ckpt.peek(path)

    def test_keep_last_2_rotation(self, tmp_path):
        path = str(tmp_path / "c.npz")
        first = _tree()
        ckpt.save(path, first)
        second = {"a": first["a"] + 1.0, "n": np.int64(6)}
        ckpt.save(path, second)
        np.testing.assert_array_equal(ckpt.peek(path)["a"], second["a"])
        prev = ckpt.peek(path + ckpt.PREV_SUFFIX)
        np.testing.assert_array_equal(prev["a"], first["a"])

    def test_legacy_artifact_without_sha_still_loads(self, tmp_path):
        path = str(tmp_path / "legacy.npz")
        np.savez(path[:-4], **{k: np.asarray(v)
                               for k, v in _tree().items()})
        raw = ckpt.peek(path)
        np.testing.assert_array_equal(raw["a"], _tree()["a"])

    def test_missing_file_raises_typed_corrupt(self, tmp_path):
        with pytest.raises(CheckpointCorrupt, match="does not exist"):
            ckpt.peek(str(tmp_path / "never.npz"))


# --------------------------------------------------------------------------
# RetryPolicy
# --------------------------------------------------------------------------
class TestRetryPolicy:
    def test_capped_exponential_delays(self):
        rp = RetryPolicy(max_attempts=6, base_delay_s=0.05, cap_delay_s=0.3)
        assert [rp.delay_s(a) for a in range(1, 6)] == \
            [0.05, 0.1, 0.2, 0.3, 0.3]

    def test_wait_uses_the_injected_sleep(self):
        slept = []
        rp = RetryPolicy(base_delay_s=0.5, sleep=slept.append)
        rp.wait(1)
        rp.wait(2)
        assert slept == [0.5, 1.0]

    def test_validate_plan_rejects_bad_policies(self):
        with pytest.raises(ValueError, match="max_attempts"):
            validate_plan(ExecPlan(retry=RetryPolicy(max_attempts=0)),
                          seeds=8, n_rows=1)
        with pytest.raises(ValueError, match="nonnegative"):
            validate_plan(ExecPlan(retry=RetryPolicy(base_delay_s=-1.0)),
                          seeds=8, n_rows=1)

    def test_asdict_records_the_sleep_by_name(self):
        plan = ExecPlan(retry=RetryPolicy(sleep=_ch))
        d = plan.asdict()
        assert d["retry"]["sleep"] == _ch.__qualname__
        assert d["retry"]["max_attempts"] == 3
        assert ExecPlan().asdict()["retry"] is None


# --------------------------------------------------------------------------
# chunk-level fault isolation: the headline bit-identity invariant
# --------------------------------------------------------------------------
def _retry(**kw):
    kw.setdefault("max_attempts", 3)
    kw.setdefault("sleep", lambda dt: None)  # no wall-clock sleeps
    return RetryPolicy(**kw)


class TestChunkRetry:
    def test_k_faults_bit_identical_moments(self, mc):
        args = (mc, [_ch(), _ch(noise_std=1.0)], "gbma", [0.01, 0.02],
                STEPS, SEEDS)
        plan = ExecPlan(seed_chunk=2, keep_seed_curves=False)
        clean = run_mc(*args, plan=plan)
        slept = []
        with ChunkFaultSchedule({0: 1, 4: 2}) as faults:
            survived = run_mc(*args, plan=plan.replace(
                retry=_retry(sleep=slept.append)))
        assert len(faults.fired) == 3  # k = 3 injected faults
        assert slept == [0.05, 0.05, 0.1]  # backoff restarts per chunk
        np.testing.assert_array_equal(survived.mean, clean.mean)
        np.testing.assert_array_equal(survived.ci95, clean.ci95)

    def test_k_faults_bit_identical_curves(self, mc):
        args = (mc, [_ch()], "gbma", [0.01], STEPS, SEEDS)
        plan = ExecPlan(seed_chunk=2)
        clean = run_mc(*args, plan=plan)
        with ChunkFaultSchedule({2: 1, 6: 1}):
            survived = run_mc(*args, plan=plan.replace(retry=_retry()))
        np.testing.assert_array_equal(survived.risks, clean.risks)
        np.testing.assert_array_equal(survived.cum_energy,
                                      clean.cum_energy)
        np.testing.assert_array_equal(survived.mean, clean.mean)

    def test_no_retry_policy_fails_fast(self, mc):
        with ChunkFaultSchedule({0: 1}):
            with pytest.raises(RuntimeError, match="injected chunk fault"):
                run_mc(mc, [_ch()], "gbma", [0.01], STEPS, SEEDS,
                       plan=ExecPlan(seed_chunk=2, keep_seed_curves=False))

    def test_exhausted_budget_reraises(self, mc):
        plan = ExecPlan(seed_chunk=2, keep_seed_curves=False,
                        retry=_retry(max_attempts=2))
        with ChunkFaultSchedule({2: 2}) as faults:  # needs 3 attempts
            with pytest.raises(RuntimeError, match="injected chunk fault"):
                run_mc(mc, [_ch()], "gbma", [0.01], STEPS, SEEDS,
                       plan=plan)
        assert len(faults.fired) == 2  # both attempts burned

    def test_checkpoint_save_stays_outside_the_retry_scope(
            self, mc, tmp_path, monkeypatch):
        """A failing ckpt.save is NOT a chunk fault: it propagates even
        under a retry policy (the interrupted-resume contract depends on
        fail-fast saves)."""
        def dying_save(path, tree):
            raise RuntimeError("simulated disk death")

        monkeypatch.setattr(ckpt, "save", dying_save)
        with pytest.raises(RuntimeError, match="disk death"):
            run_mc(mc, [_ch()], "gbma", [0.01], STEPS, SEEDS,
                   plan=ExecPlan(seed_chunk=2, keep_seed_curves=False,
                                 retry=_retry()),
                   resume_dir=str(tmp_path))


_PROP_CACHE = {}


def _prop_baseline():
    """Cached (args, plan, fault-free result) for the property test —
    module-level because the hypothesis shim's wrapper signature hides
    pytest fixtures from the collector."""
    if not _PROP_CACHE:
        mc = MSDProblem.make(N, dim=D).to_mc()
        args = (mc, [_ch()], "gbma", [0.01], STEPS, SEEDS)
        plan = ExecPlan(seed_chunk=2, keep_seed_curves=False)
        _PROP_CACHE["x"] = (args, plan, run_mc(*args, plan=plan))
    return _PROP_CACHE["x"]


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_random_fault_schedules_preserve_moments(seed):
    """Property: ANY fault schedule within the retry budget leaves the
    final reduced moments identical to the fault-free run."""
    args, plan, clean = _prop_baseline()
    rng = np.random.default_rng(seed)
    schedule = {off: int(rng.integers(0, 3))
                for off in range(0, SEEDS, 2) if rng.random() < 0.6}
    with ChunkFaultSchedule(schedule) as faults:
        survived = run_mc(*args, plan=plan.replace(
            retry=_retry(max_attempts=3)))
    assert len(faults.fired) == sum(schedule.values())
    np.testing.assert_array_equal(survived.mean, clean.mean)
    np.testing.assert_array_equal(survived.ci95, clean.ci95)


# --------------------------------------------------------------------------
# resume fallback on corrupt checkpoints
# --------------------------------------------------------------------------
class TestResumeFallback:
    def _interrupted(self, mc, tmp_path, monkeypatch):
        """Run to completion once (leaves main + .prev artifacts)."""
        args = (mc, [_ch()], "gbma", [0.01], STEPS, SEEDS)
        kw = dict(plan=ExecPlan(seed_chunk=2, keep_seed_curves=False),
                  resume_dir=str(tmp_path))
        return args, kw, run_mc(*args, **kw)

    def test_corrupt_main_falls_back_to_prev(self, mc, tmp_path,
                                             monkeypatch):
        args, kw, clean = self._interrupted(mc, tmp_path, monkeypatch)
        main = str(tmp_path / exec_mod._RESUME_FILE)
        assert int(ckpt.peek(main)["next_off"]) == SEEDS
        assert int(ckpt.peek(main + ckpt.PREV_SUFFIX)["next_off"]) \
            == SEEDS - 2
        torn_write(main)
        offs = []
        real_merge = exec_mod._mc_moments_merge

        def counting_merge(am, am2, n_prev, *a, **k):
            offs.append(int(np.asarray(n_prev)))
            return real_merge(am, am2, n_prev, *a, **k)

        monkeypatch.setattr(exec_mod, "_mc_moments_merge", counting_merge)
        with pytest.warns(UserWarning, match="corrupt resume checkpoint"):
            resumed = run_mc(*args, **kw)
        assert offs == [SEEDS - 2]  # resumed from .prev: one chunk redone
        np.testing.assert_array_equal(resumed.mean, clean.mean)
        np.testing.assert_array_equal(resumed.ci95, clean.ci95)

    def test_both_corrupt_restarts_fresh_with_warning(self, mc, tmp_path,
                                                      monkeypatch):
        args, kw, clean = self._interrupted(mc, tmp_path, monkeypatch)
        main = str(tmp_path / exec_mod._RESUME_FILE)
        torn_write(main)
        open(main + ckpt.PREV_SUFFIX, "wb").close()
        with pytest.warns(UserWarning, match="restarting the sweep"):
            restarted = run_mc(*args, **kw)
        np.testing.assert_array_equal(restarted.mean, clean.mean)
        np.testing.assert_array_equal(restarted.ci95, clean.ci95)

    def test_foreign_fingerprint_still_rejected(self, mc, tmp_path):
        kw = dict(plan=ExecPlan(seed_chunk=2, keep_seed_curves=False),
                  resume_dir=str(tmp_path))
        run_mc(mc, [_ch()], "gbma", [0.01], STEPS, SEEDS, **kw)
        with pytest.raises(ValueError, match="fingerprint"):
            run_mc(mc, [_ch()], "gbma", [0.02], STEPS, SEEDS, **kw)


# --------------------------------------------------------------------------
# node dropout / partial participation
# --------------------------------------------------------------------------
class TestParticipation:
    def test_full_participation_is_bit_identical(self, mc):
        args = (mc, [_ch()], "gbma", [0.01], STEPS, SEEDS)
        base = run_mc(*args)
        on = run_mc(*args, participation=1.0)
        np.testing.assert_array_equal(on.risks, base.risks)
        np.testing.assert_array_equal(on.cum_energy, base.cum_energy)
        np.testing.assert_array_equal(on.mean, base.mean)
        per_row = run_mc(*args, participation=[1.0])
        np.testing.assert_array_equal(per_row.risks, base.risks)

    def test_dropout_changes_results_and_costs_energy(self, mc):
        args = (mc, [_ch()], "gbma", [0.01], STEPS, SEEDS)
        base = run_mc(*args)
        dropped = run_mc(*args, participation=0.6)
        assert not np.array_equal(dropped.mean, base.mean)
        # silent nodes transmit nothing, so the energy ledger moves too
        assert not np.array_equal(dropped.cum_energy, base.cum_energy)

    def test_validation(self, mc):
        args = (mc, [_ch()], "gbma", [0.01], STEPS, SEEDS)
        with pytest.raises(ValueError, match="participation"):
            run_mc(*args, participation=0.0)
        with pytest.raises(ValueError, match="participation"):
            run_mc(*args, participation=1.5)
        with pytest.raises(ValueError, match="one participation per row"):
            run_mc(*args, participation=[0.5, 0.9])

    def test_per_row_p_sweep_is_one_compile(self, mc):
        if not exec_mod.clear_cache():
            pytest.skip("jit cache clearing unavailable")
        run_mc(mc, [_ch()] * 3, "gbma", [0.01] * 3, STEPS, SEEDS,
               participation=[0.9, 0.7, 0.5], keep_seed_curves=False)
        assert exec_mod.trace_count() == 1

    def test_full_participation_shares_the_resume_fingerprint(
            self, mc, tmp_path):
        """p = 1.0 is the no-knob workload: a checkpoint written without
        the knob short-circuits a participation=1.0 rerun (no foreign-
        fingerprint error), while p < 1 IS a different workload."""
        args = (mc, [_ch()], "gbma", [0.01], STEPS, SEEDS)
        kw = dict(plan=ExecPlan(seed_chunk=2, keep_seed_curves=False),
                  resume_dir=str(tmp_path))
        first = run_mc(*args, **kw)
        again = run_mc(*args, participation=1.0, **kw)
        np.testing.assert_array_equal(again.mean, first.mean)
        with pytest.raises(ValueError, match="fingerprint"):
            run_mc(*args, participation=0.5, **kw)

    def test_chunked_dropout_matches_single_shot(self, mc):
        args = (mc, [_ch()], "gbma", [0.01], STEPS, SEEDS)
        single = run_mc(*args, participation=0.7)
        chunked = run_mc(*args, participation=0.7,
                         plan=ExecPlan(seed_chunk=2))
        # the dropout stream is counter-based, so chunks draw the same
        # masks; XLA rounds a 2-seed vmap and a 6-seed vmap differently
        # in the last ulp (with or without participation), hence the
        # same bound as the chunked-vs-full test of test_exec.py
        np.testing.assert_allclose(chunked.risks, single.risks, rtol=1e-6,
                                   atol=1e-10)
        np.testing.assert_allclose(chunked.mean, single.mean, rtol=1e-6,
                                   atol=1e-10)

    def test_memory_model_counts_the_mask_stream(self):
        base = exec_mod.estimate_peak_bytes(
            n_rows=2, seeds=8, steps=10, n_max=16, dim=4)
        on = exec_mod.estimate_peak_bytes(
            n_rows=2, seeds=8, steps=10, n_max=16, dim=4,
            participation_on=True)
        assert on["rng_draw_bytes"] - base["rng_draw_bytes"] \
            == 2 * 8 * 10 * 16 * 4  # rows * seeds * steps * n_max * f32
