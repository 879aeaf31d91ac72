"""The reduction of the program's own spans and scopes
(`chipbench/program_trace.py`), on hand-built traces whose every number is
known, on HLO that XLA compiled here, and on a trace recorded here."""
import glob
import json
import os
import sys

import pytest

from chipbench import program_trace as P
from chipbench import trace as T


# --------------------------------------------------------------------------
# hand-built traces: ProfileData's shape, and the protobuf the trace keeps
# --------------------------------------------------------------------------
class Ev:
    def __init__(self, name, start, end, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, end - start
        self.stats = list(stats.items())


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class PD:
    def __init__(self, planes):
        self.planes = planes


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*pairs):
    """A protobuf message from (field number, int | str | bytes | [int])."""
    out = b""
    for num, v in pairs:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
            continue
        if isinstance(v, list):  # packed repeated int64
            v = b"".join(_varint(x) for x in v)
        if isinstance(v, str):
            v = v.encode()
        out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _instr(name, opcode, iid, op_name=None, operands=(), called=()):
    pairs = [(1, name), (2, opcode), (35, iid)]
    if op_name is not None:
        pairs.append((7, _msg((1, opcode), (2, op_name))))
    if operands:
        pairs.append((36, list(operands)))
    if called:
        pairs.append((38, list(called)))
    return _msg(*pairs)


def _computation(name, cid, root, instrs):
    return _msg((1, name), *[(2, i) for i in instrs], (5, cid), (6, root))


MODULE = _msg(
    (1, "jit_f"), (2, "main"),
    # a fusion whose root is in the slot's scope
    (3, _computation("fused_slot", 1, 11, [
        _instr("p0", "parameter", 10),
        _instr("mul.3", "multiply", 11, "jit(f)/vmap(repro.mc.slot)/mul",
               operands=[10])])),
    # a multi-output fusion (noise draws fused into a norm's reduction):
    # the scope most of its instructions carry, whatever its tuple's order
    (3, _computation("fused_noise", 2, 23, [
        _instr("u", "xor", 20, "jit(f)/repro.gbma.noise/jit(_normal)/xor"),
        _instr("n", "erf-inv", 21, "jit(f)/repro.gbma.noise/erf_inv",
               operands=[20]),
        _instr("red", "reduce", 22, "jit(f)/repro.train.optimizer/sum",
               operands=[21]),
        _instr("t", "tuple", 23, operands=[22, 21])])),
    # a root without metadata: the scope of the instructions it fuses
    (3, _computation("fused_energy", 4, 42, [
        _instr("sq", "multiply", 40, "jit(f)/vmap(repro.mc.energy)/mul"),
        _instr("c", "constant", 41),
        _instr("acc", "add", 42, operands=[40, 41])])),
    (3, _computation("main", 3, 35, [
        _instr("fusion.1", "fusion", 30, "jit(f)/elsewhere", called=[1]),
        _instr("fusion.2", "fusion", 31, called=[2]),
        _instr("while.1", "while", 32, "jit(f)/repro.mc.scan/while"),
        _instr("copy.1", "copy", 33),
        _instr("fusion.3", "fusion", 36, called=[4]),
        _instr("custom-call.1", "custom-call", 34,
               "jit(f)/transpose(jvp(repro.train.loss_grad))/pallas_call"),
        _instr("tuple.9", "tuple", 35, operands=[30, 34])])),
)


def _xspace(module=MODULE, name="jit_f(7)"):
    hlo_proto = _msg((1, module))
    stat = _msg((1, 5), (6, hlo_proto))
    meta_plane = _msg(
        (2, "/host:metadata"),
        (5, _msg((1, 5), (2, _msg((1, 5), (2, "Hlo Proto"))))),
        (4, _msg((1, 7), (2, _msg((1, 7), (2, name), (5, stat))))))
    return _msg((1, _msg((2, "/host:CPU"))), (1, meta_plane))


def _op(name, start, end):
    return Ev(f"%{name} = f32[4]{{0}} op(f32[4] %x)", start, end)


def _pd():
    """One device, a 100 ns window. Device: fusion.1 [0, 10), while.1
    [10, 60) around fusion.2 [20, 30), copy.1 [40, 45) and fusion.3
    [50, 55), custom-call.1 [70, 80): busy 70, idle [60, 70) and
    [80, 100). Host: run_mc [0, 95)
    around prepare [0, 5) and dispatch [60, 90), which holds one chunk
    [62, 68); the harness's own sweep.call covers the whole window."""
    device = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_f(7)", 0, 100)]),
        Line("XLA Ops", [_op("fusion.1", 0, 10), _op("while.1", 10, 60),
                         _op("fusion.2", 20, 30), _op("copy.1", 40, 45),
                         _op("fusion.3", 50, 55),
                         _op("custom-call.1", 70, 80)])])
    host = Plane("/host:CPU", [Line("python", [
        Ev("window", 0, 100), Ev("sweep.call", 0, 100),
        Ev("repro.run_mc", 0, 95, call=1, rows=2),
        Ev("repro.run_mc.prepare", 0, 5, call=1),
        Ev("repro.run_mc.dispatch", 60, 90, call=1, node_slots=12,
           padded_node_slots=16),
        Ev("repro.run_mc.chunk", 62, 68, call=1, chunk=0, attempt=1),
        Ev("repro.run_mc", 120, 130, call=2)])])  # after the window
    return PD([device, host])


def test_idle_goes_to_the_innermost_span():
    p = P.summarize(_pd(), _xspace())
    ns = {k: round(v * 1e9, 6) for k, v in p.span_idle_s.items()}
    # [60, 70): dispatch 2 + chunk 6 + dispatch 2; [80, 100): dispatch 10,
    # run_mc 5, and 5 that no repro. span covers
    assert ns == {"repro.run_mc.dispatch": 14, "repro.run_mc.chunk": 6,
                  "repro.run_mc": 5}
    own = {k: round(v * 1e9, 6) for k, v in p.span_self_s.items()}
    assert own == {"repro.run_mc.prepare": 5, "repro.run_mc": 60,
                   "repro.run_mc.dispatch": 24, "repro.run_mc.chunk": 6}
    assert p.window_s == pytest.approx(100e-9)


def test_stats_sum_over_the_window_spans():
    p = P.summarize(_pd(), _xspace())
    assert p.span_stats == {
        "repro.run_mc": {"call": 1, "rows": 2},
        "repro.run_mc.prepare": {"call": 1},
        "repro.run_mc.dispatch": {"call": 1, "node_slots": 12,
                                  "padded_node_slots": 16},
        "repro.run_mc.chunk": {"call": 1, "chunk": 0, "attempt": 1}}
    assert P.stat_sums([("s", 0, 1, {"n": 2, "x": "text"}),
                        ("s", 5, 6, {"n": 3})], 0, 10) == {"s": {"n": 5}}


def test_device_time_grouped_by_scope():
    p = P.summarize(_pd(), _xspace())
    ns = {k: round(v * 1e9, 6) for k, v in p.scope_s.items()}
    assert ns == {"repro.mc.slot": 10,  # the fusion's root, not its own
                  "repro.mc.scan": 30,  # the while, less what it encloses
                  "repro.gbma.noise": 10,  # most of a tuple fusion
                  "repro.mc.energy": 5,  # most of a bare root's fusion
                  P.UNSCOPED: 5,  # no metadata
                  "repro.train.loss_grad": 10}  # transforms taken off
    s = T.summarize(_pd(), window_span="window")
    assert sum(p.scope_s.values()) == pytest.approx(s.busy_s)
    top = p.breakdown()["device_scopes"][0]
    assert top == ["repro.mc.scan", pytest.approx(30e-9)]
    assert p.scope_ops["repro.mc.scan"] == [["while.1",
                                             pytest.approx(30e-9)]]


def test_readings_of_a_hand_built_window():
    pd = _pd()
    p, s = P.summarize(pd, _xspace()), T.summarize(pd, window_span="window")
    assert P.pad_share(p, s, {}) == pytest.approx(25.0)
    assert P.run_mc_idle_share(p, s, {}) == pytest.approx(25.0)
    assert P.scoped_share(p, s, {}) == pytest.approx(100 * (1 - 5 / 70))
    assert P.gbma_share(p, s, {}) == pytest.approx(100 * 10 / 70)
    run = {"ota_slot": {"work": {"flops": 10, "bytes": 819},
                        "calls": 2},
           "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 819e9}}
    # least 2 ns over the slot scope's 10 ns
    assert P.ota_slot_roofline(p, s, run) == pytest.approx(20.0)


def test_without_the_programs_spans_nothing_is_read():
    pd = _pd()
    pd.planes[1].lines[0].events = pd.planes[1].lines[0].events[:2]
    p, s = P.summarize(pd, _xspace()), T.summarize(pd, window_span="window")
    assert p.span_idle_s == {} and p.span_stats == {}
    assert P.pad_share(p, s, {}) is None
    assert P.run_mc_idle_share(p, s, {}) is None


def test_without_hlo_every_operation_is_unscoped():
    p = P.summarize(_pd(), _msg((1, _msg((2, "/host:metadata")))))
    assert set(p.scope_s) == {P.UNSCOPED}


@pytest.mark.parametrize("path, scope", [
    ("jit(f)/repro.mc.slot/div", "repro.mc.slot"),
    ("jit(f)/vmap(vmap(repro.mc.draws))/vmap()/sqrt", "repro.mc.draws"),
    ("jit(f)/repro.mc.scan/while/body/closed_call/repro.mc.grad/mul",
     "repro.mc.grad"),
    ("jit(step)/repro.train.loss_grad/transpose(jvp())/while",
     "repro.train.loss_grad"),
    ("jit(f)/while/body/add", P.UNSCOPED),
    ("", P.UNSCOPED),
])
def test_scope_of_an_op_name_path(path, scope):
    assert P.scope_of(path) == scope


def test_scopes_of_hlo_that_xla_compiled():
    """The field numbers the reader walks, checked against a module XLA
    serialized: each fusion takes its root's scope."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("repro.a"):
            y = jnp.sin(x) * 2
        with jax.named_scope("repro.b"):
            z = jax.vmap(lambda r: jnp.tanh(r).sum())(y)
        return y, z

    c = f.lower(jnp.ones((8, 128))).compile()
    proto = c.runtime_executable().hlo_modules()[0]
    scopes = P.hlo_scopes(proto.as_serialized_hlo_module_proto())
    text = c.as_text()
    entry = text[text.index("ENTRY"):]
    fusions = {}
    for line in entry.splitlines():
        if " fusion(" in line:
            name = line.split("=", 1)[0].strip().lstrip("%")
            root_scope = scopes[name]
            fusions[name] = root_scope
    assert set(fusions.values()) >= {"repro.a", "repro.b"}
    assert "repro.a" in scopes.values() and "repro.b" in scopes.values()


def test_committed_cpu_trace_has_no_program_spans():
    """The committed trace predates the program's spans: the existing
    reduction reads it as before and this one finds no span and no
    scope."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "cpu_window.xplane.pb")
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_file(path)
    p = P.summarize(pd, raw)
    s = T.summarize(pd, window_span="window",
                    span_names=("sweep.call", "sweep.post"))
    assert p.span_idle_s == {} and p.span_stats == {}
    assert set(p.scope_s) == {P.UNSCOPED}
    assert p.scope_s[P.UNSCOPED] == pytest.approx(s.busy_s, rel=1e-9)


def test_recorded_run_mc_window(tmp_path):
    """A padded `run_mc` traced on this machine: its spans, stats and
    scopes come back through the reduction."""
    import jax
    from jax.profiler import ProfileData

    from benchmarks.common import MSDProblem
    from repro.core.channel import ChannelConfig
    from repro.core.mc import run_mc

    probs = [MSDProblem.make(n, dim=3).to_mc() for n in (4, 8)]
    ch = [ChannelConfig(fading="rayleigh", noise_std=0.5)] * 2

    def call():
        return run_mc(probs, ch, "gbma", [0.05, 0.05], 3, 2)

    call()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            call()
            call()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as f:
        raw = f.read()
    p = P.summarize(ProfileData.from_file(path), raw)
    st = p.span_stats["repro.run_mc.dispatch"]
    assert (st["node_slots"], st["padded_node_slots"]) == (24, 32)
    assert {"repro.mc.slot", "repro.mc.grad", "repro.mc.draws"} <= set(
        p.scope_s)
    assert set(p.span_self_s) >= {"repro.run_mc", "repro.run_mc.dispatch",
                                  "repro.run_mc.fetch"}


def test_pad_share_metric_reads_the_programs_counters(monkeypatch):
    from chipbench import harness
    from chipbench.tests import tiny
    from repro.core.mc import engine

    cat = harness.Catalog(tiny.ROOT, tiny.spec())
    reader = cat.module("metrics", "pad_share.padded")
    for c in (engine._NODE_SLOTS, engine._PADDED_NODE_SLOTS):
        monkeypatch.setattr(c, "_n", 0)
    assert reader.read({}) is None  # nothing dispatched yet
    engine._NODE_SLOTS.add(1500)
    engine._PADDED_NODE_SLOTS.add(3200)
    assert reader.read({}) == pytest.approx(53.125)
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # a program
    assert reader.read({}) is None  # without counters


def test_profile_tool_on_a_tiny_cell(tmp_path, monkeypatch, capsys):
    """`program_profile.py` end to end at test size, the look for a chip
    skipped: its line carries the readings that find something."""
    import jax

    from chipbench import harness, program_profile
    from chipbench.tests import tiny

    cat = tiny.catalog(str(tmp_path), ["msd_rayleigh.nsweep"])
    monkeypatch.setattr(harness, "Catalog", lambda *a, **k: cat)
    monkeypatch.setattr(harness, "require_chips",
                        lambda chips, peaks: jax.devices()[:1])
    monkeypatch.setattr(harness, "peak_for",
                        lambda kind, peaks: tiny.CPU_PEAK)
    # the chip's compile cache in the checkout is no place for CPU code
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    assert program_profile.main(["--workload", "msd_rayleigh.nsweep",
                                 "--seed", str(2**31 + 11),
                                 "--seconds", "0.5"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    r = out["readings"]
    assert r["pad_share"] == pytest.approx(100 * (1 - 112 / 192))
    assert 0 < r["run_mc_idle_share"] < 100
    assert r["ota_slot_roofline"] > 0
    assert "padded_sweep_s" in out["traced_end_to_end"]
    assert out["breakdown"]["program_idle"]
    assert out["breakdown"]["device_scopes"]
