"""The serving engine's host spans in a profiler trace, and what they say
about the host's side of each engine step.

``ServingEngine.step`` writes one ``engine.step`` span a step and, inside
it, one span per phase: ``engine.retire``, ``engine.admit``,
``engine.prefill_chunk``, ``engine.page_scatter``, ``engine.prefill.wait``,
``engine.decode.prepare``, ``engine.decode.dispatch``,
``engine.decode.wait`` and ``engine.emit``.  A name that ends in
``.wait`` is the host blocked on the device.  ``bench.trace.load`` keeps
only the harness's own ``bench.*`` spans, so the engine's are read here,
from the trace file the traced run writes, and reduced on plain
``(name, start_s, end_s)`` tuples like the rest of the trace.

Idle gaps are named here by self time: each instant of a gap goes to the
innermost span covering it (the one that started last), or to "none";
the gap takes the name with the most time in it, "none" losing ties.
On the harness's spans alone, which do not nest, that is the name
``bench.trace`` gives.  To see it for a run::

    BENCH_KEEP_TRACE=1 python3 -m bench.run --workload <cell> ... --trace 1
    python3 -m bench.spans <the .xplane.pb it names>
"""

from __future__ import annotations

import bisect
import heapq
import json
import os
import sys
from collections import defaultdict
from pathlib import Path

from bench import spec
from bench import trace as tr

ENGINE_PREFIX = "engine."
STEP = "engine.step"
DISPATCH = "engine.decode.dispatch"
WAIT_SUFFIX = ".wait"


def load(path: str | Path) -> list[tuple[str, float, float]]:
    """Every ``engine.*`` host span of a ``.xplane.pb``, in seconds on
    the trace's clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(ENGINE_PREFIX)]


def trace_file() -> Path | None:
    """The trace that ``bench.run`` is writing in this process (it reads
    the per-layer metrics before it removes the trace), or None."""
    d = spec.ROOT / ".bench_out" / f"trace-{os.getpid()}"
    found = sorted(d.rglob("*.xplane.pb"))
    return found[-1] if found else None


def of_run(run) -> list[tuple[str, float, float]]:
    """The host spans of a traced run: those its loaded trace holds, and
    the engine's from its trace file where the trace holds none."""
    spans = list(run.trace.spans)
    if any(n.startswith(ENGINE_PREFIX) for n, _, _ in spans):
        return spans
    path = trace_file()
    return spans + load(path) if path is not None else spans


def _inside(spans, name: str, t0: float, t1: float):
    return sorted((a, b) for n, a, b in spans
                  if n == name and t0 <= a and b <= t1)


def host_step_s(spans, t0: float, t1: float) -> list[float]:
    """For each ``engine.step`` wholly inside [t0, t1]: its duration less
    the time its ``*.wait`` children cover."""
    waits = sorted((a, b) for n, a, b in spans if n.endswith(WAIT_SUFFIX))
    starts = [a for a, _ in waits]
    out = []
    for a, b in _inside(spans, STEP, t0, t1):
        lo, hi = bisect.bisect_left(starts, a), bisect.bisect_left(starts, b)
        blocked = sum(e - s for s, e in tr.union(tr.clip(waits[lo:hi], a, b)))
        out.append(b - a - blocked)
    return out


def admission_stalls_s(spans, t0: float, t1: float) -> list[float]:
    """For each ``engine.step`` wholly inside [t0, t1] that dispatches a
    decode: the time from its start to its first ``engine.decode.dispatch``
    (what the decoding slots waited on retirement, admission and
    prefill before their token)."""
    starts = sorted(a for n, a, _ in spans if n == DISPATCH)
    out = []
    for a, b in _inside(spans, STEP, t0, t1):
        i = bisect.bisect_left(starts, a)
        if i < len(starts) and starts[i] < b:
            out.append(starts[i] - a)
    return out


def innermost(spans) -> list[tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of the time the spans
    cover, each put down to the innermost span covering it: the one that
    started last, the shorter on a tie."""
    edges = sorted({x for _, a, b in spans for x in (a, b)})
    todo = sorted(spans, key=lambda s: s[1])
    live, i, out = [], 0, []
    for lo, hi in zip(edges, edges[1:]):
        while i < len(todo) and todo[i][1] <= lo:
            name, a, b = todo[i]
            heapq.heappush(live, (-a, b, name))
            i += 1
        while live and live[0][1] <= lo:  # ended: drop it
            heapq.heappop(live)
        if live:
            out.append((lo, hi, live[0][2]))
    return out


def self_time(spans, t0: float, t1: float) -> dict[str, float]:
    """Seconds inside [t0, t1] that each span name holds as the
    innermost span: where the host's time went, by phase."""
    out: dict[str, float] = defaultdict(float)
    for a, b, name in innermost([s for s in spans if s[0] != tr.WINDOW_SPAN]):
        if b > t0 and a < t1:
            out[name] += min(b, t1) - max(a, t0)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def name_gaps(trace: tr.Trace, spans, device: int = 0):
    """Every idle gap of ``device`` in the trace's window as (name,
    seconds), named after the span with the most self time in it."""
    pieces = innermost([s for s in spans if s[0] != tr.WINDOW_SPAN])
    starts = [p[0] for p in pieces]
    busy = trace.busy_intervals(device)
    edges = [trace.t0] + [x for iv in busy for x in iv] + [trace.t1]
    out = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        by = defaultdict(float)
        j = max(0, bisect.bisect_right(starts, s) - 1)
        while j < len(pieces) and pieces[j][0] < e:
            a, b, name = pieces[j]
            cover = min(b, e) - max(a, s)
            if cover > 0:
                by[name] += cover
            j += 1
        by["none"] = (e - s) - sum(by.values())
        name = max(by, key=lambda k: (by[k], k != "none", k))
        out.append((name, e - s))
    return out


def idle_by_span(gaps) -> dict[str, float]:
    out: dict[str, float] = defaultdict(float)
    for name, sec in gaps:
        out[name] += sec
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        raise SystemExit("usage: python3 -m bench.spans <trace.xplane.pb>")
    from bench import window as win

    t = tr.load(argv[0])
    engine = load(argv[0])
    gaps = name_gaps(t, t.spans + engine)
    host = self_time(t.spans + engine, t.t0, t.t1)
    print(f"spans: host self time by span {json.dumps(host)}")
    print(f"spans: idle by span {json.dumps(idle_by_span(gaps))}")
    longest = sorted(gaps, key=lambda g: -g[1])[:10]
    print(f"spans: longest gaps {json.dumps(longest)}")
    steps = host_step_s(engine, t.t0, t.t1)
    stalls = admission_stalls_s(engine, t.t0, t.t1)
    print(f"spans: engine steps {len(steps)}, host_step_ms "
          f"{1e3 * sum(steps) / len(steps) if steps else None}, "
          f"admission_stall_p99_ms "
          f"{1e3 * win.percentile(stalls, 99) if stalls else None}")


if __name__ == "__main__":
    main()
