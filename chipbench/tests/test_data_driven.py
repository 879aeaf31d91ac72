"""The harness is driven by data: every entry of BENCHMARK.json is found
by its name, each cell's traffic comes out the same from the same seed,
and a new configuration, traffic mix and metric join through files
alone."""
import json
import os

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import tiny

SPEC = tiny.spec()
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def _cat():
    return harness.Catalog(tiny.ROOT, SPEC)


def test_contract_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "chipbench/run.py"]
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_entry_found_by_name(workload):
    cat = _cat()
    cell = cat.cell(workload)
    assert cell["config"]["name"] == cell["workload"]["config"]
    kind = cat.module("generators", cell["traffic"]["generator"])
    work = kind.Workload(cell["config"], cell["traffic"], 1, [], cat)
    assert callable(work.ref.__dict__.get("row") or work.ref.train)
    assert cell["traffic"]["limits"]
    for m in cell["per_layer"]:
        assert callable(cat.module("metrics", m["name"]).read)
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traffic_same_from_same_seed(workload):
    cat = _cat()
    cell = cat.cell(workload)
    kind = cat.module("generators", cell["traffic"]["generator"])

    def inputs(seed):
        return kind.Workload(cell["config"], cell["traffic"], seed, [],
                             cat).inputs()

    seed = 2**31 + 977  # wider than 32 signed bits
    a, b, c = inputs(seed), inputs(seed), inputs(seed + 1)
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)
    assert any(not np.array_equal(np.asarray(a[k]), np.asarray(c[k]))
               for k in a)


def test_new_cell_from_files_alone(tmp_path):
    """A throwaway configuration, traffic mix and per-layer metric, each a
    file of its own in a directory the harness searches, run as a cell."""
    extra = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics"):
        (extra / sub).mkdir(parents=True)
    cat = _cat()
    cfg = cat.config("msd_rayleigh")
    cfg.update(name="toy_quadratic")
    cfg["problem"]["dim"] = 6
    (extra / "configs" / "toy_quadratic.json").write_text(json.dumps(cfg))
    (extra / "traffic" / "toy_quadratic.mini.json").write_text(json.dumps({
        "generator": "sweep", "rows": {"n_nodes": [12, 12],
                                       "energy_eps": [0.5, 1.5]},
        "steps": 10, "seeds": 4, "run_mc": {"shard_seeds": False},
        "check_sweeps": 2, "ref_block": 4,
        "limits": {"risk_gap": 1e-5, "mean_gap": 1e-5}}))
    (extra / "metrics" / "calls_per_s.py").write_text(
        "def read(ctx):\n"
        "    return ctx['run']['calls'] / ctx['run']['window_s']\n")
    spec = {
        "configs": [{"name": "toy_quadratic", "source": "x",
                     "file": str(extra / "configs" / "toy_quadratic.json"),
                     "reduced": [], "why": "x"}],
        "workloads": [{"name": "toy_quadratic.mini",
                       "config": "toy_quadratic", "traffic": "mini",
                       "chips": 1, "why": "x"}],
        "end_to_end": [m for m in SPEC["end_to_end"]
                       if m["name"] in ("setup_s", "sweep_s")],
        "per_layer": [{"name": "calls_per_s", "unit": "1/s",
                       "better": "higher", "source": "host_clock",
                       "layer": "harness", "moves": "sweep_s",
                       "workloads": ["toy_quadratic.mini"]}],
    }
    for m in spec["end_to_end"]:
        m.pop("workloads", None)
    toy = harness.Catalog(str(tmp_path), spec, extra_dirs=[str(extra)])
    plain = tiny.run(toy, "toy_quadratic.mini")
    assert plain["correct"] and set(plain["metrics"]) == {"setup_s",
                                                          "sweep_s"}
    traced = tiny.run(toy, "toy_quadratic.mini", trace=True)
    assert traced["correct"]
    assert set(traced["metrics"]) == {"calls_per_s"}
    assert traced["metrics"]["calls_per_s"]["value"] > 0
    assert list(traced)[-1] == "checks"
    assert traced["device"]["busy_s"] > 0
