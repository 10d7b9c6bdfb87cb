"""The trace reduction on a synthetic trace with known answers."""

import pytest

from bench import trace


def make():
    # window [0, 10]; device 0 busy [1,3] u [2,4] u [6,7], one op past
    # the window end; host spans cover the gaps
    ops = [[("fusion.1", 1.0, 3.0), ("paged_kernel", 2.0, 4.0),
            ("fusion.1", 6.0, 7.0), ("late", 9.5, 11.0)]]
    modules = [[("jit_serve_step", 1.0, 4.0), ("jit_prefill_step", 6.0, 7.0)]]
    spans = [("bench.window", 0.0, 10.0), ("bench.step", 0.5, 4.5),
             ("bench.submit", 4.5, 5.0), ("bench.step", 5.0, 9.0),
             ("bench.generate", 5.5, 5.9)]
    return trace.Trace(0.0, 10.0, ops, modules, spans)


def test_union_merges_overlaps():
    assert trace.union([(2, 4), (1, 3), (6, 7)]) == [(1, 4), (6, 7)]
    assert trace.union([]) == []


def test_busy_and_idle_share():
    t = make()
    # busy: [1,4] + [6,7] + [9.5,10] clipped = 4.5
    assert t.busy_s() == pytest.approx(4.5)
    assert t.window_s == 10.0
    assert t.idle_share() == pytest.approx(0.55)


def test_busy_averages_devices():
    t = make()
    t.ops.append([("x", 0.0, 10.0)])
    assert t.busy_s() == pytest.approx((4.5 + 10.0) / 2)


def test_time_by_name_and_matching():
    t = make()
    by = t.time_by_name("ops")
    assert by["fusion.1"] == pytest.approx(3.0)
    assert by["paged_kernel"] == pytest.approx(2.0)
    assert by["late"] == pytest.approx(0.5)  # clipped to the window
    assert t.matching("modules", "serve_step") == [pytest.approx(3.0)]
    assert t.matching("ops", "late") == []  # not wholly inside
    assert t.top_ops(2) == [["fusion.1", pytest.approx(3.0)],
                            ["paged_kernel", pytest.approx(2.0)]]


def test_idle_gaps_attributed_to_spans():
    t = make()
    gaps = t.idle_gaps(0)
    # gaps: [0,1] step(0.5-4.5 covers 0.5), [4,6] submit covers 0.5,
    # step 5-9 covers 1.0 -> step; [7,9.5] step
    assert [g[0] for g in gaps] == ["bench.step", "bench.step", "bench.step"]
    assert [g[1] for g in gaps] == [pytest.approx(1.0), pytest.approx(2.0),
                                    pytest.approx(2.5)]
    assert t.longest_gaps(1) == [["bench.step", pytest.approx(2.5)]]
    assert sum(t.idle_by_span().values()) == pytest.approx(5.5)


def test_innermost_span_wins_a_tie():
    t = trace.Trace(0.0, 4.0, [[("op", 0.0, 1.0), ("op", 3.0, 4.0)]], [[]],
                    [("bench.window", 0.0, 4.0), ("bench.step", 0.0, 4.0),
                     ("bench.generate", 1.0, 3.0)])
    assert t.idle_gaps(0) == [("bench.generate", pytest.approx(2.0))]


def test_gap_outside_any_span():
    t = trace.Trace(0.0, 2.0, [[("op", 1.0, 2.0)]], [[]],
                    [("bench.window", 0.0, 2.0)])
    assert t.idle_gaps(0) == [("none", pytest.approx(1.0))]


def test_kernel_time_inside_its_program():
    ops = [[("%a = custom-call tpu_custom_call", 1.0, 1.5),
            ("%b = fusion", 1.5, 2.0),
            ("%c = custom-call tpu_custom_call", 6.2, 6.6),
            ("%d = custom-call tpu_custom_call", 8.0, 8.5)]]
    modules = [[("jit_serve_step(1)", 1.0, 4.0),
                ("jit_prefill_step(2)", 6.0, 7.0)]]
    t = trace.Trace(0.0, 10.0, ops, modules, [("bench.window", 0.0, 10.0)])
    assert t.kernel_s("serve_step") == pytest.approx(0.5)
    assert t.kernel_s("prefill_step") == pytest.approx(0.4)
    assert t.kernel_s("verify_step") == 0.0
