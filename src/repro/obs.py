"""The program's own observability: host spans, device scopes, counters.

Everything the program emits is named `repro.<layer>...` and lands in
JAX's profiler trace (`jax.profiler.trace`), on one clock with the
device's operations; docs/performance.md ("Profiling a sweep or a
training step") lists every name and what it covers.

* Host spans are `jax.profiler.TraceAnnotation`s. Their stats (the
  annotation's keyword arguments) are stored on the trace event. With no
  profiler running a span costs about a microsecond. `CallSpans` opens
  one call's span and its consecutive phases, which share the call's
  per-process sequence number (`call`).
* Device scopes are `jax.named_scope`s inside jitted code: they land in
  each HLO op's `op_name` metadata (`jit(f)/.../repro.mc.slot/...`), cost
  nothing at run time and change no numerics.
* `Counter` is a running count, safe to add to from several threads; a
  named one is listed by `counters()`.
"""
from __future__ import annotations

import itertools
import threading
from typing import Optional

import jax

_CALLS = itertools.count(1)  # next() on a count is atomic in CPython
_COUNTERS: dict = {}


class CallSpans:
    """The span `name` around one call, and consecutive child spans
    `name.<phase>` inside it. `phase()` ends the open child and opens the
    next; `end_phase()` ends it; leaving the `with` block ends both. Every
    span carries the call's `call` number, so a trace groups a call's
    phases (and spans opened elsewhere with `spans.call`) by it."""

    def __init__(self, name: str, **stats):
        self.name = name
        self.call = next(_CALLS)
        self._outer = jax.profiler.TraceAnnotation(name, call=self.call,
                                                   **stats)
        self._child = None

    def __enter__(self) -> "CallSpans":
        self._outer.__enter__()
        return self

    def phase(self, phase: str, **stats) -> None:
        self.end_phase()
        self._child = jax.profiler.TraceAnnotation(
            f"{self.name}.{phase}", call=self.call, **stats)
        self._child.__enter__()

    def end_phase(self) -> None:
        if self._child is not None:
            child, self._child = self._child, None
            child.__exit__(None, None, None)

    def __exit__(self, *exc) -> None:
        self.end_phase()
        self._outer.__exit__(*exc)


class Counter:
    """A running integer count. A named counter is listed by
    `counters()` under its name."""

    def __init__(self, name: Optional[str] = None):
        self._lock = threading.Lock()
        self._n = 0
        if name is not None:
            _COUNTERS[name] = self

    def add(self, n: int = 1) -> None:
        with self._lock:
            self._n += n

    def value(self, reset: bool = False) -> int:
        """The count; `reset=True` also zeroes it."""
        with self._lock:
            n = self._n
            if reset:
                self._n = 0
        return n


def counters() -> dict:
    """{name: count} of every named counter."""
    return {name: c.value() for name, c in _COUNTERS.items()}
