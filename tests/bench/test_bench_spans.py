"""The engine's host spans: naming idle gaps by self time, the two
readers on synthetic traces with known answers, and the spans read back
from a real profiler trace of the engine on the CPU."""

from types import SimpleNamespace

import pytest

from bench import spans, spec, trace


def harness_traces():
    """The harness-only traces of ``test_bench_trace``: there the rule
    gives the names ``bench.trace`` gives."""
    ops = [[("fusion.1", 1.0, 3.0), ("paged_kernel", 2.0, 4.0),
            ("fusion.1", 6.0, 7.0), ("late", 9.5, 11.0)]]
    yield trace.Trace(0.0, 10.0, ops, [[]],
                      [("bench.window", 0.0, 10.0), ("bench.step", 0.5, 4.5),
                       ("bench.submit", 4.5, 5.0), ("bench.step", 5.0, 9.0),
                       ("bench.generate", 5.5, 5.9)])
    yield trace.Trace(0.0, 4.0, [[("op", 0.0, 1.0), ("op", 3.0, 4.0)]], [[]],
                      [("bench.window", 0.0, 4.0), ("bench.step", 0.0, 4.0),
                       ("bench.generate", 1.0, 3.0)])
    yield trace.Trace(0.0, 2.0, [[("op", 1.0, 2.0)]], [[]],
                      [("bench.window", 0.0, 2.0)])


@pytest.mark.parametrize("t", list(harness_traces()))
def test_self_time_names_agree_on_harness_spans(t):
    assert spans.name_gaps(t, t.spans) == t.idle_gaps(0)


def engine_trace():
    """Window [0, 20]; two engine steps inside two harness steps.  The
    device runs the decode of step 1 over [3, 8] and of step 2 over
    [13, 18].  Idle: [0, 3], [8, 13] (straddles the steps), [18, 20]."""
    ops = [[("decode", 3.0, 8.0), ("decode", 13.0, 18.0)]]
    host = [
        ("bench.window", 0.0, 20.0),
        ("bench.step", 0.5, 9.0), ("engine.step", 0.6, 8.9),
        ("engine.retire", 0.6, 0.8), ("engine.admit", 0.8, 1.0),
        ("engine.decode.prepare", 1.0, 1.5),
        ("engine.decode.dispatch", 1.5, 3.0),
        ("engine.decode.wait", 3.0, 8.0), ("engine.emit", 8.0, 8.9),
        ("bench.submit", 9.2, 9.4),
        ("bench.step", 9.5, 19.0), ("engine.step", 9.6, 18.9),
        ("engine.retire", 9.6, 9.8), ("engine.admit", 9.8, 10.0),
        ("engine.prefill_chunk", 10.0, 10.5),
        ("engine.page_scatter", 10.5, 10.7),
        ("engine.prefill.wait", 10.7, 12.6),
        ("engine.decode.prepare", 12.6, 12.7),
        ("engine.decode.dispatch", 12.7, 13.0),
        ("engine.decode.wait", 13.0, 18.0), ("engine.emit", 18.0, 18.9),
    ]
    return trace.Trace(0.0, 20.0, ops, [[]], host)


def test_innermost_phase_names_a_gap_straddling_two_steps():
    t = engine_trace()
    gaps = spans.name_gaps(t, t.spans)
    # [8, 13]: emit 0.9, bench.step 0.2, none 0.3, submit 0.2, retire
    # 0.2, admit 0.2, prefill_chunk 0.5, page_scatter 0.2, prefill.wait
    # 1.9, prepare 0.1, dispatch 0.3
    assert gaps[1] == ("engine.prefill.wait", pytest.approx(5.0))
    # [0, 3]: dispatch 1.5 > none 0.5, prepare 0.5, ...
    assert gaps[0] == ("engine.decode.dispatch", pytest.approx(3.0))
    # [18, 20]: emit 0.9 < none 1.0 (after the last step)
    assert gaps[2] == ("none", pytest.approx(2.0))
    # bench.trace puts all three down to the outer harness span or none
    assert [g[0] for g in t.idle_gaps(0)] == ["bench.step", "bench.step",
                                              "bench.step"]
    by = spans.idle_by_span(gaps)
    assert list(by) == ["engine.prefill.wait", "engine.decode.dispatch",
                        "none"]


def test_none_loses_a_tie():
    t = trace.Trace(0.0, 2.0, [[]], [[]],
                    [("bench.window", 0.0, 2.0), ("engine.emit", 0.0, 1.0)])
    assert spans.name_gaps(t, t.spans) == [("engine.emit",
                                            pytest.approx(2.0))]


def test_innermost_pieces():
    got = spans.innermost([("a", 0.0, 10.0), ("b", 2.0, 4.0),
                           ("c", 3.0, 5.0), ("d", 12.0, 13.0)])
    assert got == [(0.0, 2.0, "a"), (2.0, 3.0, "b"), (3.0, 4.0, "c"),
                   (4.0, 5.0, "c"), (5.0, 10.0, "a"), (12.0, 13.0, "d")]


def test_self_time_by_phase():
    t = engine_trace()
    got = spans.self_time(t.spans, 0.0, 10.0)
    assert got["engine.decode.wait"] == pytest.approx(5.0)
    assert got["engine.emit"] == pytest.approx(0.9)
    assert got["bench.step"] == pytest.approx(0.3)  # outside engine.step
    assert got["engine.admit"] == pytest.approx(0.4)  # clipped at 10
    assert "engine.step" not in got  # its phases tile it
    # all of [0, 10] but [0, 0.5], [9.0, 9.2] and [9.4, 9.5]
    assert sum(got.values()) == pytest.approx(9.2)


def run_of(t):
    return SimpleNamespace(trace=t)


def test_host_step_ms_reader():
    t = engine_trace()
    # step 1: 8.3 s less the 5.0 s decode wait; step 2: 9.3 s less 1.9 s
    # prefill wait and 5.0 s decode wait
    read = spec.metric_reader("host_step_ms")
    assert read(run_of(t)) == pytest.approx(1e3 * (3.3 + 2.4) / 2)
    assert spans.host_step_s(t.spans, 0.0, 10.0) == [pytest.approx(3.3)]


def test_admission_stall_reader():
    t = engine_trace()
    read = spec.metric_reader("admission_stall_p99_ms")
    # step starts to first dispatch: 0.9 and 3.1; nearest-rank p99
    assert spans.admission_stalls_s(t.spans, 0.0, 20.0) == [
        pytest.approx(0.9), pytest.approx(3.1)]
    assert read(run_of(t)) == pytest.approx(3100.0)


def test_a_step_without_a_decode_has_no_stall():
    host = [("engine.step", 1.0, 2.0), ("engine.admit", 1.0, 1.5),
            ("engine.step", 3.0, 5.0), ("engine.decode.dispatch", 4.0, 4.5)]
    assert spans.admission_stalls_s(host, 0.0, 10.0) == [pytest.approx(1.0)]
    # a step cut by the window is left out
    assert spans.admission_stalls_s(host, 0.0, 4.0) == []


def test_readers_find_nothing_without_engine_spans(monkeypatch):
    monkeypatch.setattr(spans, "trace_file", lambda: None)
    t = trace.Trace(0.0, 4.0, [[("op", 0.0, 1.0)]], [[]],
                    [("bench.window", 0.0, 4.0), ("bench.step", 0.0, 4.0)])
    for name in ("host_step_ms", "admission_stall_p99_ms"):
        assert spec.metric_reader(name)(run_of(t)) is None


def test_engine_spans_from_a_real_trace(tmp_path, monkeypatch):
    """A traced engine on the CPU: the readers find its steps through
    the trace file, with the harness's trace holding none of them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config
    from repro.models import transformer as tf
    from repro.serve.engine import ServingEngine

    cfg = get_config("qwen3_0p6b").scaled_down(num_layers=2, d_model=64,
                                               vocab=256)
    eng = ServingEngine(tf.init(jax.random.PRNGKey(0), cfg, jnp.float32),
                        cfg, max_slots=2, max_len=64, page_size=8,
                        prefill_chunk=8)
    eng.submit(np.arange(11, dtype=np.int32), 3)
    eng.step()  # compile outside the trace
    eng.submit(np.arange(5, dtype=np.int32), 3)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        eng.run()
    jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    got = spans.load(path)
    names = {n for n, _, _ in got}
    assert {"engine.step", "engine.admit", "engine.prefill.wait",
            "engine.decode.dispatch", "engine.decode.wait"} <= names
    assert not any(n.startswith("bench.") for n in names)
    window = [(a, b) for n, a, b in got if n == "engine.step"]
    t0, t1 = window[0][0], window[-1][1]
    t = trace.Trace(t0, t1, [[]], [[]], [("bench.window", t0, t1)])
    monkeypatch.setattr(spans, "trace_file", lambda: path)
    steps = spans.host_step_s(spans.of_run(run_of(t)), t0, t1)
    assert len(steps) == len(window) and all(s > 0 for s in steps)
    assert spec.metric_reader("admission_stall_p99_ms")(run_of(t)) > 0
