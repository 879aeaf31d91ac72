"""Back-compat guard for the montecarlo -> repro.core.mc package split.

Every name that was importable from `repro.core.montecarlo` (and
re-exported through `repro.core`) before the split must still resolve
through the shim — downstream scripts and notebooks import both the
public API and, in tests, the underscore sampler helpers. The shim must
also stay *live*: registry-derived views (`ALGOS`, `PROBLEMS`) reflect
late `register_*` calls.
"""
import importlib

import pytest

# the public surface of the pre-split module
PUBLIC_NAMES = [
    "ALGOS",
    "ChannelBatch",
    "MCProblem",
    "MCProblemBatch",
    "MCResult",
    "clear_cache",
    "energy_to_target",
    "localization_mc_problem",
    "quadratic_mc_problem",
    "run_mc",
    "trace_count",
]

# private helpers exercised by tests / notebooks against the old module
PRIVATE_NAMES = [
    "_OTA_ALGOS",
    "_BLIND_ALGOS",
    "_PER_NODE_FIELDS",
    "_ROW_FNS",
    "_antenna_keys",
    "_check_prefix_stable",
    "_magnitude_m2",
    "_mc_core",
    "_node_mask",
    "_ota_slot",
    "_resolve_n_shards",
    "_row_complex_gains",
    "_row_gains",
    "_row_normal",
    "_sample_complex_gains",
    "_sample_gains",
    "_sample_magnitude",
    "_slot_update",
]


@pytest.mark.parametrize("name", PUBLIC_NAMES + PRIVATE_NAMES)
def test_name_resolves_through_the_shim(name):
    mod = importlib.import_module("repro.core.montecarlo")
    assert getattr(mod, name) is not None, (
        f"repro.core.montecarlo.{name} no longer resolves — the "
        "back-compat shim over repro.core.mc lost it")


def test_shim_objects_are_the_package_objects():
    """The shim re-exports, it does not duplicate: engine state (the
    compile counter, the jit cache) must be shared."""
    shim = importlib.import_module("repro.core.montecarlo")
    engine = importlib.import_module("repro.core.mc.engine")
    problems = importlib.import_module("repro.core.mc.problems")
    sampling = importlib.import_module("repro.core.mc.sampling")
    assert shim.run_mc is engine.run_mc
    assert shim._mc_core is engine._mc_core
    assert shim.trace_count is engine.trace_count
    assert shim.MCProblem is problems.MCProblem
    assert shim._sample_gains is sampling._sample_gains


def test_repro_core_reexports_still_resolve():
    core = importlib.import_module("repro.core")
    for name in core.__all__:
        assert getattr(core, name) is not None, f"repro.core.{name} broke"
    # the historical montecarlo re-exports specifically
    for name in ("ChannelBatch", "MCProblem", "MCResult", "run_mc",
                 "localization_mc_problem", "quadratic_mc_problem"):
        assert getattr(core, name) is not None


def test_algos_view_is_live(monkeypatch):
    """Registering a new algorithm shows up through the shim's ALGOS (the
    old module-level tuple is now a registry view)."""
    from repro.core.mc import slots

    shim = importlib.import_module("repro.core.montecarlo")
    before = shim.ALGOS
    assert "test_dummy_algo" not in before
    monkeypatch.setitem(
        slots.ALGO_REGISTRY, "test_dummy_algo",
        slots.AlgoSpec(name="test_dummy_algo",
                       slot_fn=slots._centralized_slot))
    assert "test_dummy_algo" in shim.ALGOS
    assert "test_dummy_algo" not in shim._OTA_ALGOS  # not flagged ota


def test_problems_view_is_live(monkeypatch):
    from repro.core.mc import problems

    shim = importlib.import_module("repro.core.montecarlo")
    assert set(shim._PER_NODE_FIELDS) == set(problems.PROBLEMS)
    spec = problems.PROBLEMS["quadratic"]
    monkeypatch.setitem(problems.PROBLEMS, "test_dummy_problem", spec)
    assert "test_dummy_problem" in shim._PER_NODE_FIELDS
    assert shim._ROW_FNS["test_dummy_problem"] == (spec.grad_row,
                                                   spec.risk_row)


def test_duplicate_registration_is_rejected():
    from repro.core.mc.problems import PROBLEMS, register_problem
    from repro.core.mc.slots import ALGO_REGISTRY, register_algo

    spec = PROBLEMS["quadratic"]
    with pytest.raises(ValueError):
        register_problem("quadratic", spec.grad_row, spec.risk_row,
                         spec.pad_values)
    with pytest.raises(ValueError):
        register_algo("gbma", ALGO_REGISTRY["gbma"].slot_fn)
