"""The yardstick's counts against hand counts at each cell's shapes, and
the table of peaks."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import counting, harness
from chipbench.tests import tiny


@pytest.mark.parametrize("t, n, d, flops, nbytes", [
    # msd_rayleigh.energy: 3 rows x 256 seeds, N=500, d=90
    (768, 500, 90, 69_120_000, 4 * (34_560_000 + 384_000 + 138_240)),
    # msd_rayleigh.nsweep's largest row: 256 seeds, N=800, d=90
    (256, 800, 90, 36_864_000, 4 * (18_432_000 + 204_800 + 46_080)),
])
def test_ota_slot_counts_unpadded_shapes(t, n, d, flops, nbytes):
    work = counting.ota_slot(t, n, d)
    assert work == {"flops": flops, "bytes": nbytes}
    peak = harness.load_peaks()["TPU v5 lite"]
    # memory-bound: the least time is the bytes over the HBM bandwidth
    assert counting.roofline_seconds(work, peak) == pytest.approx(
        nbytes / 819e9)


def _repro100m():
    cat = harness.Catalog(tiny.ROOT, tiny.spec())
    return cat.config("repro100m_ota")["model"]


def test_decoder_parameters_by_hand():
    m = _repro100m()
    per_layer = 4 * 640 * 640 + 3 * 640 * 2560 + 2 * 640
    assert counting.decoder_params(m) == 14 * per_layer + 32000 * 640 + 640
    assert counting.decoder_params(m) == 112_248_960


def test_decoder_parameters_match_the_model():
    import jax

    from repro.configs.base import ModelConfig
    from repro.models.model import build_model

    m = _repro100m()
    shapes = build_model(ModelConfig(**m)).params_shape()
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert counting.decoder_params(m) == n


def test_training_flops_per_token_by_hand():
    m = _repro100m()
    matmul = 14 * (4 * 640 * 640 + 3 * 640 * 2560) + 32000 * 640
    assert matmul == 112_230_400
    attn = 12 * 14 * 10 * 64 * 512
    assert counting.decoder_train_flops_per_token(m, 512) == \
        6 * matmul + attn == 728_432_640


def test_peaks_table_and_unknown_kind():
    peaks = harness.load_peaks()
    v5e = harness.peak_for("TPU v5 lite", peaks)
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.NoChip):
        harness.peak_for("TPU v9 imaginary", peaks)


def test_no_tpu_is_refused():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1, harness.load_peaks())


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "msd_rayleigh.energy", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_without_tpu_prints_no_result():
    p = _run_cli(tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_without_the_program_prints_no_result(tmp_path):
    """A checkout of only BENCHMARK.json and chipbench/ has no system to
    measure."""
    import shutil

    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    with open(tmp_path / "BENCHMARK.json") as f:
        assert json.load(f)["paths"] == ["chipbench"]


def test_padded_sweep_counts_each_row_at_its_own_size():
    """The Fig. 6 sweep pads every row to N=800; its slot work is counted
    at N = 100, 200, 400 and 800: 256 seeds x 1500 nodes x 90."""
    cat = harness.Catalog(tiny.ROOT, tiny.spec())
    cell = cat.cell("msd_rayleigh.nsweep")
    kind = cat.module("generators", cell["traffic"]["generator"])
    work = kind.Workload(cell["config"], cell["traffic"], 1, [], cat)
    work.inputs()
    slot = work.counts(calls=2, elapsed=1.0)["ota_slot"]
    assert slot["work"]["flops"] == 2 * 256 * 1500 * 90
    assert slot["calls"] == 2 * 400
