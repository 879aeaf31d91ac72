"""The program's own observability (`repro.obs`): host spans of `run_mc`
recorded in a profiler trace, device scopes in the compiled HLO of the
engine and of the training step, and the counters.

Spans are read back from a trace recorded on the CPU; scopes from the
`op_name` metadata of each compiled HLO module, where `jax.named_scope`
puts them (transform wrappers such as `vmap(...)` around a scope name are
taken off before comparing)."""
import glob
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import MSDProblem
from repro import obs
from repro.core.channel import ChannelConfig
from repro.core.mc import engine
from repro.core.montecarlo import run_mc

D, STEPS, SEEDS = 3, 3, 2
MC_SCOPES = {"repro.mc.draws", "repro.mc.grad", "repro.mc.slot",
             "repro.mc.risk", "repro.mc.energy", "repro.mc.scan"}


@pytest.fixture(scope="module")
def padded():
    """Two rows, N = 4 and N = 8: one padded call."""
    return [MSDProblem.make(n, dim=D).to_mc() for n in (4, 8)]


def _ch():
    return [ChannelConfig(fading="rayleigh", noise_std=0.5)] * 2


def _traced(tmp_path, fn):
    """The `repro.` host events recorded while `fn` runs, in order:
    [(name, start_ns, end_ns, {stat: value})]."""
    from jax.profiler import ProfileData

    fn()  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _scopes(hlo_text: str) -> set:
    """Every `repro.` component of the text's op_name paths."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        for comp in path.split("/"):
            while comp.endswith(")") and "(" in comp:
                comp = comp[comp.index("(") + 1:-1]
            if comp.startswith("repro."):
                out.add(comp)
    return out


def test_run_mc_spans_nest_inside_their_call(tmp_path, padded):
    spans = _traced(tmp_path, lambda: run_mc(
        padded, _ch(), "gbma", [0.05, 0.05], STEPS, SEEDS))
    outer = [s for s in spans if s[0] == "repro.run_mc"]
    assert len(outer) == 1
    _, lo, hi, stats = outer[0]
    assert stats == {"call": stats["call"], "rows": 2, "seeds": SEEDS,
                     "steps": STEPS}
    children = [s for s in spans if s[0] != "repro.run_mc"]
    assert [s[0] for s in children] == [
        "repro.run_mc.prepare", "repro.run_mc.plan", "repro.run_mc.dispatch",
        "repro.run_mc.fetch", "repro.run_mc.stats"]
    for name, s, e, st in children:
        assert lo <= s <= e <= hi, name
        assert st["call"] == stats["call"], name
    for (_, _, e0, _), (_, s1, _, _) in zip(children, children[1:]):
        assert e0 <= s1  # consecutive phases do not overlap
    dispatch = children[2][3]
    assert dispatch["node_slots"] == 12
    assert dispatch["padded_node_slots"] == 16
    assert dispatch["trajectories"] == 2 * SEEDS


def test_each_call_has_its_own_number(tmp_path, padded):
    def two_calls():
        for _ in range(2):
            run_mc(padded, _ch(), "gbma", [0.05, 0.05], STEPS, SEEDS)

    spans = _traced(tmp_path, two_calls)
    calls = [s[3]["call"] for s in spans if s[0] == "repro.run_mc"]
    assert len(calls) == 2 and calls[1] > calls[0]
    for c in calls:
        assert sum(s[3]["call"] == c for s in spans) == 6


@pytest.mark.parametrize("keep_seed_curves", [True, False])
def test_chunked_call_has_one_span_per_chunk(tmp_path, padded,
                                             keep_seed_curves):
    spans = _traced(tmp_path, lambda: run_mc(
        padded, _ch(), "gbma", [0.05, 0.05], STEPS, 6, seed_chunk=2,
        keep_seed_curves=keep_seed_curves))
    call = next(s[3]["call"] for s in spans if s[0] == "repro.run_mc")
    dispatch = next(s for s in spans if s[0] == "repro.run_mc.dispatch")
    chunks = [s for s in spans if s[0] == "repro.run_mc.chunk"]
    assert [c[3] for c in chunks] == [
        {"call": call, "chunk": i, "attempt": 1} for i in range(3)]
    for _, s, e, _ in chunks:
        assert dispatch[1] <= s <= e <= dispatch[2]
    # the chunks fetch their own results: no call-level fetch span
    assert not any(s[0] == "repro.run_mc.fetch" for s in spans)


def test_chunk_span_records_the_attempt_that_succeeded(tmp_path, padded):
    from repro.core.mc import exec as exec_mod
    from repro.core.mc.plan import ExecPlan, RetryPolicy

    failed = set()

    def fail_once(info):
        if info["off"] == 2 and info["off"] not in failed:
            failed.add(info["off"])
            raise RuntimeError("planted fault")

    plan = ExecPlan(seed_chunk=2, keep_seed_curves=False,
                    retry=RetryPolicy(max_attempts=2, base_delay_s=0.0))
    remove = exec_mod.install_chunk_fault_hook(fail_once)
    try:
        spans = _traced(tmp_path, lambda: (failed.clear(), run_mc(
            padded, _ch(), "gbma", [0.05, 0.05], STEPS, 4, plan=plan)))
    finally:
        remove()
    assert [(s[3]["chunk"], s[3]["attempt"]) for s in spans
            if s[0] == "repro.run_mc.chunk"] == [(0, 1), (1, 2)]


def test_engine_hlo_names_every_mc_scope(monkeypatch, padded):
    texts = []
    core = engine._mc_core

    def spy(*args, **kwargs):
        texts.append(core.lower(*args, **kwargs).compile().as_text())
        return core(*args, **kwargs)

    monkeypatch.setattr(engine, "_mc_core", spy)
    run_mc(padded, _ch(), "gbma", [0.05, 0.05], STEPS, SEEDS)
    assert MC_SCOPES <= _scopes(texts[0])


def _tiny_step(**tcfg_kw):
    from repro.configs.registry import get_config
    from repro.core.gbma import GBMAConfig
    from repro.models.model import build_model
    from repro.optim.gd import momentum
    from repro.training.train_step import TrainConfig, build_train_step

    cfg = get_config("repro-100m").with_(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=64, vocab_size=64, logit_chunk=16, attn_block_q=8,
        attn_block_kv=8)
    m = build_model(cfg)
    params = m.init_params(jax.random.key(0))
    opt = momentum(0.05)
    tcfg = TrainConfig(gbma=GBMAConfig(n_nodes=2, channel=ChannelConfig(
        fading="rayleigh", noise_std=0.01)), **tcfg_kw)
    step = build_train_step(m, tcfg, opt)
    batch = {"tokens": jnp.zeros((4, 8), jnp.int32)}
    return jax.jit(step).lower(params, step.init_state(params), batch,
                               0).compile().as_text()


@pytest.mark.parametrize("tcfg_kw, scopes", [
    ({"aggregator": "gbma"},
     {"repro.gbma.weights", "repro.gbma.noise", "repro.train.loss_grad",
      "repro.train.optimizer"}),
    ({"aggregator": "fdm"},
     {"repro.gbma.noise", "repro.train.loss_grad", "repro.train.optimizer"}),
    ({"aggregator": "gbma", "route": "transport"},
     {"repro.transport.aggregate", "repro.train.loss_grad",
      "repro.train.optimizer"}),
])
def test_train_step_hlo_names_its_scopes(tcfg_kw, scopes):
    assert _scopes(_tiny_step(**tcfg_kw)) == scopes


def test_scopes_leave_the_numbers_alone(padded):
    """A scope is metadata: the engine's curves are the same with every
    `jax.named_scope` turned into a no-op."""
    import contextlib

    from repro.core.mc import exec as exec_mod

    ref = run_mc(padded, _ch(), "gbma", [0.05, 0.05], STEPS, SEEDS)
    exec_mod.clear_cache()
    real = jax.named_scope
    try:
        jax.named_scope = lambda name: contextlib.nullcontext()
        bare = run_mc(padded, _ch(), "gbma", [0.05, 0.05], STEPS, SEEDS)
    finally:
        jax.named_scope = real
        exec_mod.clear_cache()
    np.testing.assert_array_equal(ref.risks, bare.risks)
    np.testing.assert_array_equal(ref.cum_energy, bare.cum_energy)


def test_run_mc_counts_its_node_slots(padded):
    before = obs.counters()
    run_mc(padded, _ch(), "gbma", [0.05, 0.05], STEPS, SEEDS)
    after = obs.counters()
    assert after["repro.run_mc.node_slots"] - \
        before["repro.run_mc.node_slots"] == 12
    assert after["repro.run_mc.padded_node_slots"] - \
        before["repro.run_mc.padded_node_slots"] == 16


def test_counter_keeps_every_add_across_threads():
    c = obs.Counter()
    n_threads, n_adds = 16, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [c.add() for _ in range(n_adds)])
            for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert c.value(reset=True) == n_threads * n_adds
    assert c.value() == 0


def test_named_counters_are_listed():
    c = obs.Counter("repro.test.listed")
    c.add(3)
    assert obs.counters()["repro.test.listed"] == 3
    assert "repro.run_mc.node_slots" in obs.counters()


def test_call_spans_close_on_error(tmp_path):
    """Leaving the block by an exception ends the open phase and the
    call's span, so later spans do not nest inside them."""
    def fail_then_go_on():
        with pytest.raises(ValueError):
            with obs.CallSpans("repro.test.call") as spans:
                spans.phase("first")
                raise ValueError("boom")
        with jax.profiler.TraceAnnotation("repro.test.after"):
            pass

    spans = _traced(tmp_path, fail_then_go_on)
    assert [s[0] for s in spans] == [
        "repro.test.call", "repro.test.call.first", "repro.test.after"]
    assert max(s[2] for s in spans[:2]) <= spans[2][1]
