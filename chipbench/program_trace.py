"""Reduce a profiler trace to what the program itself recorded in it: its
`repro.` host spans and the `repro.` scopes of its device operations
(`src/repro/obs.py` lists them).

`chipbench/trace.py` times the device from outside the program; this
module adds, on the same clock:

  span_idle_s   each idle gap of the device split by the innermost
                `repro.` host span covering it (time no such span covers
                is left out), mean over devices
  span_self_s   each span's own time: the time in which it is the
                innermost `repro.` span
  span_stats    integer stats of the spans that start in the window,
                summed per span name (`call` sums too, meaninglessly)
  scope_s       each device operation's own time (`trace.self_times`),
                grouped by the innermost `repro.` component of its HLO
                `op_name` path, mean over devices; an operation with no
                such component counts as `(unscoped)`, a fusion under the
                root of its fused computation (see `hlo_scopes`)
  scope_ops     each scope's three largest operations

A device operation's event names its HLO instruction and carries no
`op_name`; the scope comes from the module's HLO, which the trace keeps
as an `Hlo Proto` stat of the `/host:metadata` plane's event metadata.
`ProfileData` does not expose event metadata, so that plane is read from
the `.xplane.pb` bytes with the small protobuf reader below. Operations
are matched to their module by time (the `XLA Modules` line of a TPU
plane) or by the `hlo_module`/`program_id` stats of a CPU event.

Span names and HLO scopes are compared as strings, never matched by
pattern.
"""
from __future__ import annotations

import dataclasses
from collections import Counter, defaultdict

from chipbench import trace as T

PREFIX = "repro."
UNSCOPED = "(unscoped)"
_MODULES_LINE = "XLA Modules"
_HLO_PROTO_STAT = "Hlo Proto"


# --------------------------------------------------------------------------
# protobuf wire format: just enough to walk XSpace and HloModuleProto
# --------------------------------------------------------------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def fields(buf) -> list:
    """[(field number, value)] of one message: ints for varint fields,
    bytes for length-delimited ones (fixed-width fields are skipped)."""
    out, i, n = [], 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = bytes(buf[i:i + ln]), i + ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        out.append((num, v))
    return out


def _first(fs, num, default=None):
    return next((v for n, v in fs if n == num), default)


def _ints(fs, num) -> list:
    """A repeated int64 field, packed or not."""
    out = []
    for n, v in fs:
        if n != num:
            continue
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


# --------------------------------------------------------------------------
# scopes from HLO
# --------------------------------------------------------------------------
def scope_of(op_name: str) -> str:
    """The innermost `repro.` component of an HLO `op_name` path, with
    transform wrappers (`vmap(...)`, `transpose(jvp(...))`) taken off;
    `(unscoped)` where there is none."""
    found = UNSCOPED
    for comp in op_name.split("/"):
        while comp.endswith(")") and "(" in comp:
            comp = comp[comp.index("(") + 1:-1]
        if comp.startswith(PREFIX):
            found = comp
    return found


def hlo_scopes(module_proto: bytes) -> dict:
    """{instruction name: scope} of every instruction of a serialized
    `HloModuleProto`. A fusion takes the scope of its fused computation's
    root; where the root is a tuple (a multi-output fusion) or carries no
    `op_name`, the scope that most of the fused instructions carry (XLA
    fuses the edge noise's draws into the gradient norm's reduction and
    into the update, and such a root says nothing of where the work
    is)."""
    comps, by_id = {}, {}
    for num, v in fields(module_proto):
        if num != 3:  # HloModuleProto.computations
            continue
        cf = fields(v)
        instrs = []
        for n2, iv in cf:
            if n2 != 2:  # HloComputationProto.instructions
                continue
            f = fields(iv)
            meta = _first(f, 7)  # HloInstructionProto.metadata
            ins = {"name": _first(f, 1, b"").decode(),
                   "opcode": _first(f, 2, b"").decode(),
                   "op_name": (_first(fields(meta), 2, b"").decode()
                               if meta is not None else ""),
                   "id": _first(f, 35, 0),
                   "operands": _ints(f, 36),
                   "called": _ints(f, 38)}
            instrs.append(ins)
            by_id[ins["id"]] = ins
        comps[_first(cf, 5, 0)] = {"root": _first(cf, 6, 0),
                                   "instrs": instrs}

    def own(ins):
        fused = comps.get(ins["called"][0]) if ins["opcode"] == "fusion" \
            and ins["called"] else None
        if fused is not None:
            root = by_id.get(fused["root"])
            if root is not None and root["opcode"] != "tuple" \
                    and root["op_name"]:
                return scope_of(root["op_name"])
            votes = Counter(scope_of(i["op_name"]) for i in fused["instrs"])
            votes.pop(UNSCOPED, None)
            if votes:
                return votes.most_common(1)[0][0]
        return scope_of(ins["op_name"])

    return {ins["name"]: own(ins) for c in comps.values()
            for ins in c["instrs"]}


def module_scopes(xspace: bytes) -> dict:
    """{module name as the trace gives it (`jit_f(7)`): {instruction:
    scope}} from the HLO protos of the `/host:metadata` plane."""
    out = {}
    for num, plane in fields(xspace):
        if num != 1:  # XSpace.planes
            continue
        pf = fields(plane)
        if _first(pf, 2, b"") != b"/host:metadata":
            continue
        stat_ids = set()
        for n, entry in pf:
            if n == 5:  # XPlane.stat_metadata map entry
                sm = fields(_first(fields(entry), 2, b""))
                if _first(sm, 2, b"").decode() == _HLO_PROTO_STAT:
                    stat_ids.add(_first(sm, 1, 0))
        for n, entry in pf:
            if n != 4:  # XPlane.event_metadata map entry
                continue
            em = fields(_first(fields(entry), 2, b""))
            name = _first(em, 2, b"").decode()
            for n2, stat in em:
                sf = fields(stat) if n2 == 5 else ()
                if sf and _first(sf, 1) in stat_ids:
                    hlo = _first(fields(_first(sf, 6, b"")), 1)
                    if hlo is not None:  # HloProto.hlo_module
                        out[name] = hlo_scopes(hlo)
    return out


# --------------------------------------------------------------------------
# host spans
# --------------------------------------------------------------------------
def program_spans(pd) -> list:
    """[(name, start_ns, end_ns, {stat: value})] of the host's `repro.`
    events, in order of start."""
    out = []
    for plane in pd.planes:
        if plane.name != T._HOST_PLANE:
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                    for e in line.events if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def innermost(spans, lo, hi) -> list:
    """Disjoint [(start, end, name)] covering the parts of [lo, hi) that
    some span covers, each named by the innermost span there: the one
    that started last (ties: the shorter)."""
    edges = sorted({lo, hi, *(t for _, s, e, *_ in spans
                              for t in (s, e) if lo < t < hi)})
    order = sorted(spans, key=lambda x: x[1])
    active, nxt, out = [], 0, []
    for a, b in zip(edges, edges[1:]):
        while nxt < len(order) and order[nxt][1] <= a:
            active.append(order[nxt])
            nxt += 1
        active = [x for x in active if x[2] > a]
        if not active:
            continue
        name = max(active, key=lambda x: (x[1], -x[2]))[0]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def split_by(intervals, segments) -> dict:
    """{name: ns} of the sorted, disjoint `intervals` that fall in each of
    the sorted, disjoint named `segments`."""
    out = defaultdict(float)
    j = 0
    for s, e in intervals:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, name = segments[k]
            out[name] += min(b, e) - max(a, s)
            k += 1
    return dict(out)


def stat_sums(spans, lo, hi) -> dict:
    """{span name: {stat: sum}} over the integer stats of the spans that
    start in [lo, hi)."""
    out = defaultdict(lambda: defaultdict(int))
    for name, s, _, stats, *_ in spans:
        if lo <= s < hi:
            for k, v in stats.items():
                if isinstance(v, int):
                    out[name][k] += v
    return {k: dict(v) for k, v in out.items()}


# --------------------------------------------------------------------------
# device operations with their modules
# --------------------------------------------------------------------------
def device_ops_by_module(pd) -> dict:
    """{device: [((module, op), start_ns, end_ns)]}: `trace.device_ops`
    with each operation's module."""
    out = {}
    for plane in pd.planes:
        m = T._TPU_PLANE.match(plane.name)
        if not m:
            continue
        ops, mods = [], []
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if line.name == T._OPS_LINE:
                ops += [(T.op_name(n), s, e) for n, s, e in evs]
            elif line.name == _MODULES_LINE:
                mods += evs
        mods.sort(key=lambda x: x[1])
        tagged, j = [], 0
        for name, s, e in sorted(ops, key=lambda x: x[1]):
            while j < len(mods) and mods[j][2] <= s:
                j += 1
            mod = mods[j][0] if j < len(mods) and mods[j][1] <= s else ""
            tagged.append(((mod, name), s, e))
        out[f"TPU:{m.group(1)}"] = tagged
    if out:
        return out
    evs = []  # a CPU trace: XLA's CPU operations stand in for one device
    for plane in pd.planes:
        if plane.name != T._HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "hlo_op" in st:
                    mod = (f"{st.get('hlo_module', '')}"
                           f"({st.get('program_id', '')})")
                    evs.append(((mod, e.name), e.start_ns,
                                e.start_ns + e.duration_ns))
    return {"cpu:0": evs}


# --------------------------------------------------------------------------
# one window
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ProgramSummary:
    window_s: float
    span_idle_s: dict  # innermost repro. span -> idle seconds, mean
    span_self_s: dict  # repro. span -> own seconds
    span_stats: dict  # repro. span -> {stat: sum}
    scope_s: dict  # repro. scope (or UNSCOPED) -> own seconds, mean
    n_devices: int
    scope_ops: dict = dataclasses.field(default_factory=dict)
    # scope -> [[operation, own seconds], ...], its three largest

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])
                    if v > 0]
        return {"program_idle": top(self.span_idle_s),
                "device_scopes": top(self.scope_s)}


def summarize(pd, xspace: bytes, *, window_span: str = "window",
              n_devices: int | None = None) -> ProgramSummary:
    """Reduce `pd` (and its raw bytes `xspace`) over the last host span
    named `window_span`, as `trace.summarize` does."""
    windows = T.host_spans(pd, [window_span])
    if not windows:
        raise RuntimeError(f"no host span {window_span!r} in the trace")
    _, lo, hi = windows[-1]
    spans = [s for s in program_spans(pd) if s[2] > lo and s[1] < hi]
    segs = innermost(spans, lo, hi)
    scopes = module_scopes(xspace)
    per_dev = device_ops_by_module(pd)
    if n_devices is not None and len(per_dev) < n_devices:
        raise RuntimeError(f"the trace holds {len(per_dev)} device(s), the "
                           f"run used {n_devices}")
    idle, op_ns = defaultdict(float), defaultdict(float)
    for evs in per_dev.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if min(e, hi) > max(s, lo)]
        busy = T.union((s, e) for _, s, e in inside)
        for name, ns in split_by(T.gaps(busy, lo, hi), segs).items():
            idle[name] += ns
        for (mod, op), ns in T.self_times(inside).items():
            op_ns[(scopes.get(mod, {}).get(op, UNSCOPED), op)] += ns
    k = len(per_dev)
    scope_ns, by_scope = defaultdict(float), defaultdict(list)
    for (scope, op), ns in op_ns.items():
        scope_ns[scope] += ns
        by_scope[scope].append([op, ns * 1e-9 / k])
    own = defaultdict(float)
    for a, b, name in segs:
        own[name] += (b - a) * 1e-9
    return ProgramSummary(
        window_s=(hi - lo) * 1e-9,
        span_idle_s={n: v * 1e-9 / k for n, v in idle.items()},
        span_self_s=dict(own),
        span_stats=stat_sums(spans, lo, hi),
        scope_s={n: v * 1e-9 / k for n, v in scope_ns.items()},
        n_devices=k,
        scope_ops={n: sorted(v, key=lambda x: -x[1])[:3]
                   for n, v in by_scope.items()})


# --------------------------------------------------------------------------
# readings: fn(program summary, trace summary, run record) -> % or None
# --------------------------------------------------------------------------
def run_mc_idle_share(program, summary, run):
    """Share of the window the device sat idle inside `repro.run_mc` or
    one of its children."""
    idle = [v for k, v in program.span_idle_s.items()
            if k == "repro.run_mc" or k.startswith("repro.run_mc.")]
    return 100.0 * sum(idle) / program.window_s if idle else None


def pad_share(program, summary, run):
    """Share of the dispatched node-slots that are padding:
    1 - sum(node_slots) / sum(padded_node_slots)."""
    st = program.span_stats.get("repro.run_mc.dispatch", {})
    if not st.get("padded_node_slots"):
        return None
    return 100.0 * (1.0 - st["node_slots"] / st["padded_node_slots"])


def ota_slot_roofline(program, summary, run):
    """The OTA slot's share of its roofline, timed by its scope: the
    least time of the window's slots (`counting.ota_slot`, as
    `ota_roofline.sweep` counts it) per device over the device time of
    `repro.mc.slot`, whichever kernel or fusion does the work."""
    from chipbench import counting

    slot_s = program.scope_s.get("repro.mc.slot", 0.0)
    if slot_s <= 0.0 or "ota_slot" not in run:
        return None
    slot = run["ota_slot"]
    least = slot["calls"] * counting.roofline_seconds(slot["work"],
                                                      run["peak"])
    return 100.0 * least / program.n_devices / slot_s


def gbma_share(program, summary, run):
    """Share of the device's busy time in GBMA's own work in a training
    step: the node weights and the edge noise."""
    s = sum(program.scope_s.get(k, 0.0)
            for k in ("repro.gbma.weights", "repro.gbma.noise"))
    return 100.0 * s / summary.busy_s if s > 0.0 else None


def scoped_share(program, summary, run):
    """Share of the device's busy time that a `repro.` scope names."""
    if summary.busy_s <= 0.0:
        return None
    return 100.0 * (1.0 - program.scope_s.get(UNSCOPED, 0.0)
                    / summary.busy_s)


READINGS = {"run_mc_idle_share": run_mc_idle_share, "pad_share": pad_share,
            "ota_slot_roofline": ota_slot_roofline, "gbma_share": gbma_share,
            "scoped_share": scoped_share}
