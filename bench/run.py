"""Run one cell of the benchmark once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files (``bench/spec.py``), makes the weights on the
chip from the seed, warms up every shape the cell uses (``setup_s``),
measures for ``--seconds``, then checks what the timed path produced
against the plain reference.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.  The last line of stdout is one JSON object; the
last lines of stderr are the numbers compared, each beside its limit.
Exits non-zero, with no result, unless JAX runs on enough TPU chips with
the program's Pallas kernels.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

STARTED = time.monotonic()
# the TPU runtime logs to a fixed directory under /tmp unless told
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import spec  # noqa: E402

_COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})
OUT_DIR = spec.ROOT / ".bench_out"


class CompileCounter:
    """Counts JAX's trace, lowering and compile events in this process."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.count += 1


class Tracer:
    """Profiler trace of the measured window, written inside the
    checkout and removed once read."""

    def __init__(self, directory):
        self.dir = directory

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def load(self):
        from bench import trace

        found = sorted(self.dir.rglob("*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return trace.load(found[-1]), found[-1]

    def remove(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def refuse_without_chips(chips: int) -> None:
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(f"bench: no TPU: JAX backend is "
                         f"{jax.default_backend()!r}")
    if len(jax.devices()) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(jax.devices())}")


def device_info(peak: int) -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d), "memory_peak_bytes": int(peak)}


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"], ctx.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        root=spec.ROOT, require_chip: bool = True) -> dict:
    """One run of one cell; returns the result line as a dict."""
    from types import SimpleNamespace

    cell = spec.load_cell(workload, root)
    if require_chip:
        from bench import program

        program.use_compile_cache()
        refuse_without_chips(cell.chips)
        program.refuse_unless_kernels()
    import jax

    from bench.peaks import peaks_for

    peaks = peaks_for(jax.devices()[0].device_kind) if require_chip else \
        {"bf16_flops": 1.0, "int8_ops": 1.0, "hbm_bytes_per_s": 1.0}
    compiles = CompileCounter()
    tracer = Tracer(OUT_DIR / f"trace-{os.getpid()}") if trace else None
    kind = cell.traffic["kind"]
    if kind == "serve":
        from bench import serve as runner
    elif kind == "train":
        from bench import train as runner
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    res = runner.run_cell(cell, seed, seconds, tracer, STARTED, compiles)
    print(f"bench: compilations inside the window: "
          f"{res['compiles_in_window']}", file=sys.stderr)
    print(f"bench: notes {json.dumps(res['notes'], default=float)}",
          file=sys.stderr)
    # a number the cell's file gives no limit is printed, not compared
    limits = cell.settings["limits"]
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in res["checks"].items() if k in limits}
    print(f"bench: not compared "
          f"{json.dumps({k: v for k, v in res['checks'].items() if k not in limits})}",
          file=sys.stderr)
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and res["failed"] == 0)
    line = {"correct": bool(correct), "attempted": int(res["attempted"]),
            "failed": int(res["failed"])}
    if trace:
        tr, path = tracer.load()
        ctx = SimpleNamespace(cell=cell, dims=cell.model.dims(cell.config),
                              peaks=peaks, trace=tr, root=root, **res)
        line["metrics"] = per_layer(cell, ctx)
        line["device"] = device_info(res["peak"])
        line["device"].update(busy_s=tr.busy_s(), window_s=tr.window_s)
        line["breakdown"] = {"device_ops": tr.top_ops(10),
                             "idle_gaps": tr.longest_gaps(10)}
        print(f"bench: idle by span {json.dumps(tr.idle_by_span())}",
              file=sys.stderr)
        if os.environ.get("BENCH_KEEP_TRACE"):
            print(f"bench: trace kept at {path}", file=sys.stderr)
        else:
            tracer.remove()
    else:
        vals = dict(res["e2e"], setup_s=res["setup_s"])
        line["metrics"] = {m["name"]: {"value": float(vals[m["name"]]),
                                       "unit": m["unit"]}
                           for m in cell.end_to_end}
        line["device"] = device_info(res["peak"])
    line["checks"] = checks
    for k, c in checks.items():
        print(f"bench: check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = run(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
