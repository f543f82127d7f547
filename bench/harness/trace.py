"""Reduction of a profiler trace to device busy time, idle share, per-span
device time, kernel time and a breakdown.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``; the reduction itself works on plain event
tuples (plane, line, name, start_ns, duration_ns) so that it can be checked
on a small recorded trace by hand:

* device operations are the events on the ops line of the first device
  plane (``/device:TPU:0``, line ``XLA Ops``);
* host spans are the events named ``bench.<layer>`` on any host line;
* the traced window runs from the first host span's start to the last
  one's end; busy time is the union of the operations' intervals inside
  it, idle share is one minus busy over the window;
* a span's device time is the busy time inside that span's intervals;
* an idle gap inside the window is labelled with the host span that
  covers most of it (``none`` where no span does).
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import NamedTuple

import numpy as np

DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int


def read_xplane(path: str) -> list[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 int(e.start_ns), int(e.duration_ns)))
    return out


_OP = re.compile(r" = .*?\b([a-z][a-z0-9_-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.95 = f32[...] fusion(...), ...`` -> ``fusion.95 fusion``:
    the HLO instruction and its operation, without shapes and operands."""
    inst = name.split(" = ", 1)[0].lstrip("%")
    m = _OP.search(name[:4096])
    return f"{inst} {m.group(1)}" if m and " = " in name else inst[:80]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


def _overlap(a: np.ndarray, b: np.ndarray) -> int:
    """Total length of the intersection of two disjoint interval sets."""
    i = j = 0
    tot = 0
    while i < len(a) and j < len(b):
        lo = max(a[i, 0], b[j, 0])
        hi = min(a[i, 1], b[j, 1])
        if hi > lo:
            tot += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return int(tot)


class Reduction:
    def __init__(self, events: list[Event], device_plane: str = DEVICE_PLANE,
                 ops_line: str = OPS_LINE):
        spans = [e for e in events if e.name.startswith(SPAN_PREFIX)
                 and not e.plane.startswith("/device")]
        ops = [e for e in events if e.plane == device_plane
               and e.line == ops_line and e.dur_ns > 0]
        if not spans:
            raise ValueError("the trace holds no bench.* host span")
        self.t0 = min(e.start_ns for e in spans)
        self.t1 = max(e.start_ns + e.dur_ns for e in spans)
        self.spans = collections.defaultdict(list)
        for e in spans:
            self.spans[e.name[len(SPAN_PREFIX):]].append(
                (e.start_ns, e.start_ns + e.dur_ns))
        self.ops = [e for e in ops if e.start_ns + e.dur_ns > self.t0
                    and e.start_ns < self.t1]
        iv = np.asarray([(max(e.start_ns, self.t0),
                          min(e.start_ns + e.dur_ns, self.t1))
                         for e in self.ops], np.int64).reshape(-1, 2)
        self.busy = _union(iv)

    @classmethod
    def from_dir(cls, trace_dir) -> "Reduction":
        files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        return cls(read_xplane(max(files, key=os.path.getmtime)))

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        return float((self.busy[:, 1] - self.busy[:, 0]).sum()) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def span_count(self, name: str) -> int:
        return len(self.spans.get(name, ()))

    def span_device_s(self, name: str) -> float:
        """Device busy seconds inside the intervals of span ``name``."""
        iv = _union(np.asarray(self.spans.get(name, []),
                               np.int64).reshape(-1, 2))
        return _overlap(self.busy, iv) * 1e-9

    def op_events(self, match) -> list[Event]:
        """Device operations whose name satisfies ``match(name)``."""
        return [e for e in self.ops if match(e.name)]

    def top_ops(self, n: int = 10) -> list:
        tot = collections.Counter()
        for e in self.ops:
            tot[short_name(e.name)] += e.dur_ns
        return [[k, v * 1e-9] for k, v in tot.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest idle gaps in the window, each named
        ``<span>`` after the host span that covers most of it."""
        edges = np.concatenate([[self.t0], self.busy.ravel(), [self.t1]])
        gaps = edges.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        order = np.argsort(gaps[:, 0] - gaps[:, 1])[:n]
        names = {k: _union(np.asarray(v, np.int64).reshape(-1, 2))
                 for k, v in self.spans.items()}
        out = []
        for g in gaps[order]:
            g2 = g.reshape(1, 2)
            cover = {k: _overlap(v, g2) for k, v in names.items()}
            best = max(cover, key=cover.get) if cover else None
            label = SPAN_PREFIX + best if best and cover[best] > 0 else "none"
            out.append([label, (g[1] - g[0]) * 1e-9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}
