"""The program's own spans and kernel names in a traced run.

``RouterService`` brackets each call into a layer with a host span named
``router.<layer>`` (``repro.serving.telemetry``: ``embed``, ``route``,
``feedback``) whose stats carry the service tick, the live rows ``n``, the
``bucket`` and, set as the span closes, the compile-path host seconds JAX
spent inside it (``compiles``, ``trace_s``, ``lower_s``, ``compile_s``).
Its kernels carry names (``sgld_potential_fwd``/``_bwd``,
``dueling_select``, ``dueling_score``) that reach the instruction name of
their ``tpu_custom_call``.

This reads the same newest ``.xplane.pb`` under ``TRACE_DIR`` that
``trace.Reduction.from_dir`` reads, this time with each event's stats,
once per file, and clips what it finds to the run's traced window
(``run.trace.t0``, ``t1``); device time comes from ``run.trace``'s
operations and busy intervals. A program without these spans or names
reads nothing: every function here then returns an empty list, 0 or
None, and never raises.
"""
from __future__ import annotations

import functools
import glob
import os
import pathlib
from typing import NamedTuple

import numpy as np

from .trace import _overlap, _union

TRACE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"
PREFIX = "router."


class Span(NamedTuple):
    name: str           # the layer, without the prefix
    start_ns: int
    end_ns: int
    stats: dict


def newest_trace() -> str | None:
    files = glob.glob(os.path.join(str(TRACE_DIR), "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=4)
def _read(path: str, mtime: float) -> tuple:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    start = int(e.start_ns)
                    out.append(Span(e.name[len(PREFIX):], start,
                                    start + int(e.duration_ns),
                                    dict(e.stats)))
    return tuple(sorted(out, key=lambda s: s.start_ns))


def read_spans(path: str) -> tuple:
    """Every ``router.*`` host span of the trace file, by start."""
    return _read(str(path), os.path.getmtime(path))


def spans(run, name: str | None = None) -> list:
    """The ``router.<name>`` spans (every router span where ``name`` is
    None) that overlap the run's window, clipped to it."""
    path = newest_trace()
    if path is None:
        return []
    t0, t1 = run.trace.t0, run.trace.t1
    return [s._replace(start_ns=max(s.start_ns, t0),
                       end_ns=min(s.end_ns, t1))
            for s in read_spans(path)
            if (name is None or s.name == name)
            and s.end_ns > t0 and s.start_ns < t1]


def kernel_s(run, name: str) -> float:
    """Device seconds in the window of the operations whose HLO
    instruction name contains ``name``."""
    t0, t1 = run.trace.t0, run.trace.t1
    ns = sum(min(e.start_ns + e.dur_ns, t1) - max(e.start_ns, t0)
             for e in run.trace.op_events(
                 lambda n: name in n.split(" = ", 1)[0]))
    return ns * 1e-9


def idle_in_spans_s(run) -> float | None:
    """Seconds of the window in which the host was inside a router span
    and no operation ran on the device; None without router spans."""
    sp = spans(run)
    if not sp:
        return None
    iv = _union(np.asarray([(s.start_ns, s.end_ns) for s in sp],
                           np.int64).reshape(-1, 2))
    inside = int((iv[:, 1] - iv[:, 0]).sum())
    return (inside - _overlap(run.trace.busy, iv)) * 1e-9
