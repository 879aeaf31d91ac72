"""Doc-drift guards: the documentation surface cannot silently diverge
from the registries it documents.

  * every `montecarlo.ALGOS` entry (the `mc.slots` algo registry) has a
    heading in `docs/algorithms.md`;
  * every `mc.problems.PROBLEMS` kind has a heading in
    `docs/montecarlo.md`'s problem-registry section;
  * every `benchmarks/fig*.py` script is registered in `benchmarks/run.py`
    and listed in the README figure table;
  * every `repro.compat.__all__` name is documented in
    `docs/algorithms.md`'s compat section;
  * the docs the README links to exist in the repo.

Adding an algorithm, a problem kind, a figure script, or a compat symbol
without documenting/registering it fails tier-1.
"""
import pathlib
import re

import jax

from repro import compat
from repro.core.montecarlo import ALGOS, PROBLEMS

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _figure_scripts():
    figs = sorted((ROOT / "benchmarks").glob("fig*.py"))
    assert len(figs) >= 7  # fig2..fig8 at time of writing
    return figs


def test_every_algo_has_a_heading_in_algorithms_md():
    text = (ROOT / "docs" / "algorithms.md").read_text()
    for algo in ALGOS:
        assert re.search(rf"^#+ .*`{algo}`", text, re.M), (
            f"algo {algo!r} is in montecarlo.ALGOS but has no heading in "
            "docs/algorithms.md — document its update rule, RNG semantics, "
            "energy accounting and slot path there")


def test_every_problem_kind_has_a_heading_in_montecarlo_md():
    text = (ROOT / "docs" / "montecarlo.md").read_text()
    for kind in PROBLEMS:
        assert re.search(rf"^#+ .*`{kind}`", text, re.M), (
            f"problem kind {kind!r} is registered in mc.problems.PROBLEMS "
            "but has no heading in docs/montecarlo.md — document its "
            "objective, risk metric and pad semantics in the problem-"
            "registry section")


def test_every_figure_script_is_registered_in_run_py():
    run_src = (ROOT / "benchmarks" / "run.py").read_text()
    for fig in _figure_scripts():
        assert fig.stem in run_src, (
            f"benchmarks/{fig.name} is not registered in benchmarks/run.py")


def test_every_figure_script_is_in_the_readme_table():
    readme = (ROOT / "README.md").read_text()
    for fig in _figure_scripts():
        assert f"benchmarks/{fig.name}" in readme, (
            f"benchmarks/{fig.name} is missing from the README figure "
            "table")


def test_compat_public_surface_is_documented():
    text = (ROOT / "docs" / "algorithms.md").read_text()
    for name in compat.__all__:
        assert f"`{name}`" in text, (
            f"repro.compat.{name} is exported (__all__) but undocumented "
            "in docs/algorithms.md")


def test_docs_claim_only_the_installed_jax():
    """The repo supports the one JAX that requirements-ci.txt pins: the
    pin is the installed version, and no doc claims another line."""
    pin = re.search(r"^jax\S*==(\S+)$",
                    (ROOT / "requirements-ci.txt").read_text(), re.M)
    assert pin and pin.group(1) == jax.__version__
    docs = [ROOT / "README.md", ROOT / ".claude/skills/verify/SKILL.md",
            *sorted((ROOT / "docs").glob("*.md"))]
    for doc in docs:
        assert not re.search(r"\b0\.4\.(x|\d+)", doc.read_text()), (
            f"{doc.name} still claims a JAX 0.4 line")


def test_readme_doc_links_resolve():
    readme = (ROOT / "README.md").read_text()
    for rel in re.findall(r"\]\((docs/[^)#]+)", readme):
        assert (ROOT / rel).is_file(), f"README links to missing {rel}"


def test_performance_md_documents_the_exec_knobs():
    """docs/performance.md is the execution layer's contract: every
    exec-layer `run_mc` knob and the benchmark artifact it explains must
    appear there, and both the README and docs/montecarlo.md must link
    it."""
    import inspect

    from repro.core.montecarlo import run_mc

    text = (ROOT / "docs" / "performance.md").read_text()
    sig = inspect.signature(run_mc)
    exec_knobs = [n for n in ("rng_plan", "seed_chunk", "keep_seed_curves")
                  if n in sig.parameters]
    assert exec_knobs, "run_mc lost its execution-layer knobs"
    for knob in exec_knobs:
        assert f"`{knob}`" in text, (
            f"run_mc({knob}=...) is an execution-layer knob but "
            "docs/performance.md does not document it")
    assert "BENCH_montecarlo.json" in text
    assert "estimate_peak_bytes" in text, (
        "performance.md must document the memory model")
    for linker in ("README.md", "docs/montecarlo.md"):
        assert "performance.md" in (ROOT / linker).read_text(), (
            f"{linker} must cross-link docs/performance.md")


def test_performance_md_documents_the_exec_plan_surface():
    """The plan/place/run/reduce pipeline is part of the execution-layer
    contract: every `ExecPlan` field, the plan entry points, the resume
    knob and the placed benchmark entry must appear in
    docs/performance.md — adding a plan field without documenting it
    fails tier-1."""
    import dataclasses

    from repro.core.mc import ExecPlan

    text = (ROOT / "docs" / "performance.md").read_text()
    for f in dataclasses.fields(ExecPlan):
        assert f"`{f.name}`" in text, (
            f"ExecPlan.{f.name} is an execution-plan field but "
            "docs/performance.md does not document it")
    for name in ("ExecPlan", "auto_plan", "resume_dir", "chan_merge",
                 "shard_map", "large_chunked_placed", "topology",
                 "fingerprint", "xla_force_host_platform_device_count"):
        assert name in text, (
            f"docs/performance.md must document {name!r} (plan/placement/"
            "resume sections)")
    bench_src = (ROOT / "benchmarks" / "bench_montecarlo.py").read_text()
    assert "large_chunked_placed" in bench_src, (
        "the documented large_chunked_placed entry left the benchmark")


def test_performance_md_documents_the_cost_model():
    """The measured cost model is part of the execution-layer contract:
    every `CalibrationConfig` knob and the artifact/consumer vocabulary
    must appear in docs/performance.md — adding a calibration knob
    without documenting it fails tier-1."""
    import dataclasses

    from repro.core.mc import CalibrationConfig

    text = (ROOT / "docs" / "performance.md").read_text()
    for f in dataclasses.fields(CalibrationConfig):
        assert f"`{f.name}`" in text, (
            f"CalibrationConfig.{f.name} is a calibration knob but "
            "docs/performance.md does not document it")
    for name in ("costmodel", "CALIBRATION_mc.json",
                 "REPRO_CALIBRATION_PATH", "predict_run_us",
                 "load_cost_model", "cached_machine_peaks",
                 'cost_model="measured"', "measured_plan",
                 "--write-bench"):
        assert name in text, (
            f"docs/performance.md must document {name!r} (measured "
            "cost model / calibration artifact section)")


def test_serving_md_pins_the_mc_server_surface():
    """docs/serving.md is the sweep-server contract: every request and
    config field must appear in its schema/knob tables, the typed errors
    and the coalescing/preemption vocabulary must be documented, the
    harness pieces it names must exist, and the README must link it."""
    import dataclasses

    from repro.serving.mc_server import McServeConfig, SweepRequest

    text = (ROOT / "docs" / "serving.md").read_text()
    for f in dataclasses.fields(SweepRequest):
        assert f"`{f.name}`" in text, (
            f"SweepRequest.{f.name} is a request field but "
            "docs/serving.md's schema table does not document it")
    for f in dataclasses.fields(McServeConfig):
        assert f"`{f.name}`" in text, (
            f"McServeConfig.{f.name} is a server knob but "
            "docs/serving.md does not document it")
    for name in ("static_signature", "estimate_peak_bytes",
                 "slice_result", "host_seed_stats", "trace_count",
                 "AdmissionError", "RequestError", "ServeError",
                 "quantum", "coalesc", "serve_sync", "serve_forever",
                 "InlineExecutor", "ManualClock", "TracingExecutor",
                 "serve_coalesce", "--selftest", "pad_flops_ratio",
                 "bucket_occupancy", "predict_run_us", "cache_epoch",
                 "shape class", "monolithic_warm_s", "`layouts`",
                 "demanded node"):
        assert name in text, (
            f"docs/serving.md must document {name!r} (signature/"
            "admission/preemption/harness sections)")
    assert (ROOT / "tests" / "_serving_harness.py").is_file()
    assert "serving.md" in (ROOT / "README.md").read_text(), (
        "README.md must cross-link docs/serving.md")


def test_fault_tolerance_docs_pin_the_retry_and_checkpoint_surface():
    """The fault-tolerance contract spans both guides: every
    `RetryPolicy` field and the checkpoint/retry vocabulary must appear
    in docs/performance.md, the serving-side degradation vocabulary in
    docs/serving.md, and the participation knob in docs/montecarlo.md —
    adding a policy field or typed error without documenting it fails
    tier-1."""
    import dataclasses

    from repro.core.mc import RetryPolicy

    perf = (ROOT / "docs" / "performance.md").read_text()
    for f in dataclasses.fields(RetryPolicy):
        assert f"`{f.name}`" in perf, (
            f"RetryPolicy.{f.name} is a retry knob but "
            "docs/performance.md does not document it")
    for name in ("RetryPolicy", "CheckpointCorrupt", "sha256",
                 "os.replace", "`.prev`", "install_chunk_fault_hook",
                 "bit-identical", "_fault_harness"):
        assert name in perf, (
            f"docs/performance.md must document {name!r} (fault-"
            "tolerance section)")
    serving = (ROOT / "docs" / "serving.md").read_text()
    for name in ("PartialResult", "QuarantinedError", "`deadline_s`",
                 "default_deadline_s", "hang_threshold_s",
                 "seeds_completed", "seeds_requested", "watchdog",
                 "deadline_expired", "quarantined", "--chaos",
                 "chaos-smoke", "ClockJump", "FlakyOnce"):
        assert name in serving, (
            f"docs/serving.md must document {name!r} (fault-tolerance "
            "section)")
    mc_doc = (ROOT / "docs" / "montecarlo.md").read_text()
    for name in ("`participation`", 'b"part"', "one compile"):
        assert name in mc_doc, (
            f"docs/montecarlo.md must document {name!r} (node-dropout "
            "section)")
    assert (ROOT / "tests" / "_fault_harness.py").is_file()


def test_training_md_pins_the_transport_surface():
    """docs/training.md is the training-route contract: every registry
    aggregator must appear in its routing table, the transport knobs it
    documents must exist on TransportConfig, and both the README and
    docs/algorithms.md must link it."""
    import dataclasses

    from repro.core.transport import TransportConfig

    text = (ROOT / "docs" / "training.md").read_text()
    for algo in ALGOS:
        assert f"`{algo}`" in text, (
            f"aggregator {algo!r} is in the MAC registry but missing from "
            "docs/training.md's routing table — say which route it takes")
    fields = {f.name for f in dataclasses.fields(TransportConfig)}
    for knob in ("block_d", "transmit_dtype", "ota_impl", "mc_steps",
                 "power_budget"):
        assert knob in fields, f"TransportConfig lost documented knob {knob}"
        assert knob in text, (
            f"TransportConfig.{knob} is undocumented in docs/training.md")
    for phrase in ("FULL_CONCAT", "init_state", "tx_energy", "grad_norm",
                   "clip_frac", "hoist_draws"):
        assert phrase in text, (
            f"docs/training.md must document {phrase!r}")
    for linker in ("README.md", "docs/algorithms.md"):
        assert "training.md" in (ROOT / linker).read_text(), (
            f"{linker} must cross-link docs/training.md")
