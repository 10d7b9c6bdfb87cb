"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy time, time per operation or program by name, and the
idle gaps between device work, each put down to the harness span the
host was in.

The reduction works on plain ``(name, start_s, end_s)`` tuples, so it is
checked on synthetic traces; ``load`` turns a JAX ``.xplane.pb`` into
them.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from pathlib import Path

#: device lines whose events are single operations / whole programs
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"
#: prefix of the spans the harness writes around its calls
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


@dataclasses.dataclass
class Trace:
    """One traced window.  ``ops[d]`` and ``modules[d]`` are the
    (name, start, end) events of device ``d``; ``spans`` the harness's
    host spans; times in seconds on one clock."""
    t0: float
    t1: float
    ops: list
    modules: list
    spans: list

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy_intervals(self, device: int):
        return union(clip([(s, e) for _, s, e in self.ops[device]],
                          self.t0, self.t1))

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over devices."""
        per = [sum(e - s for s, e in self.busy_intervals(d))
               for d in range(len(self.ops))]
        return sum(per) / len(per)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def _events(self, kind: str):
        return [ev for dev in getattr(self, kind) for ev in dev
                if ev[2] > self.t0 and ev[1] < self.t1]

    def time_by_name(self, kind: str = "ops") -> dict[str, float]:
        """Device seconds per event name inside the window, summed over
        devices, each event clipped to the window."""
        out: dict[str, float] = defaultdict(float)
        for name, s, e in self._events(kind):
            out[name] += min(e, self.t1) - max(s, self.t0)
        return dict(out)

    def matching(self, kind: str, needle: str) -> list[float]:
        """Durations of the whole events inside the window whose name
        contains ``needle``."""
        return [e - s for name, s, e in self._events(kind)
                if needle in name and s >= self.t0 and e <= self.t1]

    def kernel_s(self, program: str, marker: str = "tpu_custom_call") -> float:
        """Device seconds of the kernel calls (ops whose text holds
        ``marker``) that run inside the program events whose name holds
        ``program``, summed over devices, clipped to the window."""
        total = 0.0
        for ops, mods in zip(self.ops, self.modules):
            spans = union(clip([(s, e) for name, s, e in mods
                                if program in name], self.t0, self.t1))
            for name, s, e in ops:
                if marker not in name:
                    continue
                for a, b in spans:
                    if a <= s < b:
                        total += min(e, b) - s
                        break
        return total

    def top_ops(self, n: int = 10) -> list[list]:
        by = sorted(self.time_by_name("ops").items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in by[:n]]

    def idle_gaps(self, device: int = 0) -> list[tuple[str, float]]:
        """Every idle gap of ``device`` in the window, named after the
        innermost harness span that covers most of it ("none" if the
        host was in no span)."""
        busy = self.busy_intervals(device)
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        out = []
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                out.append((self._span_at(s, e), e - s))
        return out

    def _span_at(self, s: float, e: float) -> str:
        best, best_key = "none", None
        for name, a, b in self.spans:
            if name == WINDOW_SPAN:
                continue
            cover = min(b, e) - max(a, s)
            if cover <= 0:
                continue
            key = (cover, -(b - a))  # most overlap, then innermost
            if best_key is None or key > best_key:
                best, best_key = name, key
        return best

    def longest_gaps(self, n: int = 10) -> list[list]:
        gaps = sorted(self.idle_gaps(0), key=lambda g: -g[1])
        return [[name, sec] for name, sec in gaps[:n]]

    def idle_by_span(self) -> dict[str, float]:
        out: dict[str, float] = defaultdict(float)
        for name, sec in self.idle_gaps(0):
            out[name] += sec
        return dict(out)


def is_device_plane(plane) -> bool:
    """A chip's plane: it has a line of XLA operations (planes such as
    ``/device:CUSTOM:...`` carry none)."""
    return (plane.name.startswith("/device:")
            and any(line.name == OP_LINE for line in plane.lines))


def load(path: str | Path) -> Trace:
    """Read a JAX profiler ``.xplane.pb`` (TPU device planes and the
    host plane) into a :class:`Trace` bounded by the harness's
    ``bench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if is_device_plane(plane):
            o, m = [], []
            for line in plane.lines:
                if line.name == OP_LINE:
                    o += [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                          for ev in line.events]
                elif line.name == MODULE_LINE:
                    m += [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                          for ev in line.events]
            ops.append(o)
            modules.append(m)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIX)]
    if not ops:
        raise ValueError(f"{path}: the trace holds no device plane")
    window = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if len(window) != 1:
        raise ValueError(f"{path}: expected one {WINDOW_SPAN} span, "
                         f"found {len(window)}")
    t0, t1 = window[0]
    return Trace(t0, t1, ops, modules, spans)


def describe(path: str | Path, limit: int = 8) -> str:
    """Planes, lines and a few events of a trace: what to read before
    matching names against it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    rows = []
    for plane in pd.planes:
        rows.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            rows.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:limit]:
                rows.append(f"    {ev.name!r} start_ns={ev.start_ns} "
                            f"dur_ns={ev.duration_ns}")
    return "\n".join(rows)
