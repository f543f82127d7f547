"""Spans and compile counters at the router's layer boundaries.

``span(name, counts, **attrs)`` brackets one call into a layer of
``RouterService`` (``embed``, ``route``, ``feedback``). It opens a
``jax.profiler.TraceAnnotation`` named ``router.<name>`` that carries
``attrs`` (host numbers: the service tick, live rows, bucket), so a
profiler trace shows each call on the same timeline as the device work it
dispatched, and one formed batch's embed and route share their ``tick``.

While a span is the innermost open one on its thread, JAX's compile-path
events (``jax.monitoring``) are gathered into it: tracing to a jaxpr
(``trace_s``), lowering to MLIR (``lower_s``) and the backend compile
(``compile_s``, one of ``compiles`` per program; it wraps the persistent
compile-cache fetch, so a cache hit counts here too). Intervals of one
phase are merged, so a jit traced inside its caller's trace counts once.
As the span closes these four numbers are set on the annotation and added
to ``counts``, the owner's per-span totals.

Always on and host-only: when no profiler records, a span costs a few
microseconds of host time, and nothing here reads the device.
"""
from __future__ import annotations

import contextlib
import threading

import jax

PREFIX = "router."
# jax.monitoring's compile-path events -> the span metadata they feed
PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
          "/jax/core/compile/backend_compile_duration": "compile_s"}

_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _on_time_span(event, start, end, **_):
    phase = PHASES.get(event)
    stack = getattr(_local, "stack", None)
    if phase is not None and stack:
        stack[-1][phase].append((start, end))


# once per process: services are built by the hundred in tests
jax.monitoring.register_event_time_span_listener(_on_time_span)


def _covered(intervals) -> float:
    """Seconds covered by the union of (start, end) intervals."""
    tot, hi = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > hi:
            tot += e - max(s, hi)
            hi = e
    return tot


def open_spans() -> tuple:
    """Names of this thread's open spans, outermost first."""
    return tuple(rec["name"] for rec in getattr(_local, "stack", ()))


class CompileCounts:
    """Per span name: backend compiles, and the host seconds JAX spent
    tracing, lowering and compiling inside the span."""

    def __init__(self):
        self.compiles: dict = {}
        self.compile_s: dict = {}

    def add(self, name: str, meta: dict):
        self.compiles[name] = self.compiles.get(name, 0) + meta["compiles"]
        self.compile_s[name] = (self.compile_s.get(name, 0.0)
                                + meta["trace_s"] + meta["lower_s"]
                                + meta["compile_s"])


@contextlib.contextmanager
def span(name: str, counts: CompileCounts, **attrs):
    """Bracket one call into layer ``name`` (see the module docstring)."""
    rec = {"name": name, **{p: [] for p in PHASES.values()}}
    stack = _stack()
    stack.append(rec)
    with jax.profiler.TraceAnnotation(PREFIX + name, **attrs) as ann:
        try:
            yield
        finally:
            stack.pop()
            meta = {"compiles": len(rec["compile_s"])}
            for p in PHASES.values():
                meta[p] = _covered(rec[p])
            ann.set_metadata(**meta)
            counts.add(name, meta)
