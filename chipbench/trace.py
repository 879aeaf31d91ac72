"""Reduce a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read: device busy time, time per device operation, idle gaps
attributed to the harness's own host spans.

Device operations are the events of the `XLA Ops` line of each
`/device:TPU:<n>` plane. A trace recorded on the CPU has no device plane;
there the XLA CPU backend's operation events (host events that carry an
`hlo_op` stat) stand in for one device, which is what the committed test
trace exercises. Busy time is the union of the operation intervals inside
the window span; idle is the rest of the window. An operation's time is
its own: what the operations it encloses (the body of a `while`) cover is
theirs. Each idle gap is split
over the host spans that overlap it, by overlap, and what no span covers
is `host.other`.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"
OTHER = "host.other"


def load_trace(trace_dir: str):
    """The `ProfileData` of the one `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    return ProfileData.from_file(paths[0])


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def op_name(event_name: str) -> str:
    """The HLO instruction's name: a TPU trace names an operation by its
    whole instruction text, `%name = shape op(...)`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_ops(pd) -> dict:
    """{device: [(name, start_ns, end_ns), ...]} of device operations."""
    out = {}
    for plane in pd.planes:
        m = _TPU_PLANE.match(plane.name)
        if not m:
            continue
        evs = []
        for line in plane.lines:
            if line.name == _OPS_LINE:
                evs += [(op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        out[f"TPU:{m.group(1)}"] = evs
    if out:
        return out
    evs = []  # a CPU trace: XLA's CPU operations stand in for one device
    for plane in pd.planes:
        if plane.name != _HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if "hlo_op" in _stats(e):
                    evs.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return {"cpu:0": evs}


def host_spans(pd, names) -> list:
    """[(name, start_ns, end_ns)] of host events named in `names`."""
    names = set(names)
    out = []
    for plane in pd.planes:
        if plane.name != _HOST_PLANE:
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name in names]
    return sorted(out, key=lambda s: s[1])


def union(intervals) -> list:
    """Merged, sorted [(start, end)] covering the same points."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def self_times(events) -> dict:
    """{name: ns} of each operation's own time: an operation that encloses
    others (a `while` around its body's operations) keeps only the part
    that none of them covers."""
    out = defaultdict(float)
    stack = []  # enclosing (name, start, end, [children])

    def close(frame):
        name, s, e, kids = frame
        out[name] += (e - s) - sum(b - a for a, b in union(kids))

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][2]:
            close(stack.pop())
        for frame in stack:  # every enclosing operation loses this time
            frame[3].append((s, min(e, frame[2])))
        stack.append((name, s, e, []))
    while stack:
        close(stack.pop())
    return dict(out)


def gaps(busy, lo, hi) -> list:
    """The parts of [lo, hi) that `busy` (merged, sorted) leaves free."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def attribute(gap_list, spans) -> dict:
    """{span name: ns} of the gaps' time, split by overlap over the host
    spans; time no span covers goes to `host.other`."""
    out = defaultdict(float)
    for gs, ge in gap_list:
        covered = []
        for name, s, e in spans:
            lo, hi = max(gs, s), min(ge, e)
            if hi > lo:
                out[name] += hi - lo
                covered.append((lo, hi))
        out[OTHER] += (ge - gs) - sum(e - s for s, e in union(covered))
    return dict(out)


@dataclasses.dataclass
class TraceSummary:
    """The reduced trace of one window, per device averaged over devices."""

    window_s: float
    busy_s: float
    op_s: dict  # operation name -> own seconds, mean over devices
    idle_s: dict  # host span name (or host.other) -> seconds, mean
    n_devices: int

    def ops_matching(self, pattern: str) -> float:
        """Seconds (mean over devices) of operations whose name matches
        the regular expression `pattern`; 0.0 where none does."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(((k, v) for k, v in self.idle_s.items() if v > 0),
                      key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}


def summarize(pd, *, window_span: str, span_names: tuple = (),
              n_devices: int | None = None) -> TraceSummary:
    """Reduce `pd` over the host span named `window_span` (the last one,
    if the window was opened more than once)."""
    windows = host_spans(pd, [window_span])
    if not windows:
        raise RuntimeError(f"no host span {window_span!r} in the trace")
    _, lo, hi = windows[-1]
    spans = [s for s in host_spans(pd, span_names)
             if s[2] > lo and s[1] < hi]
    per_dev = device_ops(pd)
    if n_devices is not None and len(per_dev) < n_devices:
        raise RuntimeError(f"the trace holds {len(per_dev)} device(s), the "
                           f"run used {n_devices}")
    busy_total, op_total, idle_total = 0.0, defaultdict(float), \
        defaultdict(float)
    for evs in per_dev.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if min(e, hi) > max(s, lo)]
        busy = union((s, e) for _, s, e in inside)
        busy_total += sum(e - s for s, e in busy)
        for n, ns in self_times(inside).items():
            op_total[n] += ns
        for name, ns in attribute(gaps(busy, lo, hi), spans).items():
            idle_total[name] += ns
    k = len(per_dev)
    ns = 1e-9 / k
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * ns,
        op_s={n: v * ns for n, v in op_total.items()},
        idle_s={n: v * ns for n, v in idle_total.items()}, n_devices=k)
