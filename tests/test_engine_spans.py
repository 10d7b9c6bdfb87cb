"""The serving engine's host spans: every phase of a step lies inside one
``engine.step``, phases never overlap, and the waits and attributes
match the requests served.  The spans are recorded in memory on the
engine's own clock, with no profiler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import transformer as tf
from repro.serve import engine as engine_mod
from repro.serve.engine import ServingEngine

_CACHE = {}


def _cfg_params():
    if not _CACHE:
        cfg = get_config("qwen3_0p6b").scaled_down(num_layers=2, d_model=64,
                                                   vocab=256)
        _CACHE["cfg"] = cfg
        _CACHE["params"] = tf.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    return _CACHE["cfg"], _CACHE["params"]


class Recorder:
    """Stands in for the profiler's span classes: keeps (name, start,
    end, attrs) on a fake clock that the engine's ``_now`` shares."""

    def __init__(self):
        self.t = 0.0
        self.spans = []

    def now(self):
        self.t += 1.0
        return self.t

    def span(self, name, **attrs):
        rec = self

        class Span:
            def __enter__(self):
                self.row = [name, rec.now(), None, dict(attrs)]
                return self

            def set_metadata(self, **kw):
                self.row[3].update(kw)

            def __exit__(self, *exc):
                self.row[2] = rec.now()
                rec.spans.append(tuple(self.row))

        return Span()

    def named(self, name):
        return [s for s in self.spans if s[0] == name]


def serve(monkeypatch, budget):
    """A two-chunk prompt, a second request admitted while the first
    decodes, both retired."""
    rec = Recorder()
    monkeypatch.setattr(engine_mod, "_now", rec.now)
    monkeypatch.setattr(engine_mod, "_span", rec.span)
    monkeypatch.setattr(engine_mod, "_step_span", rec.span)
    cfg, params = _cfg_params()
    eng = ServingEngine(params, cfg, max_slots=2, max_len=64, page_size=8,
                        prefill_chunk=8, prefill_budget=budget)
    rng = np.random.default_rng(3)
    first = eng.submit(rng.integers(0, cfg.vocab, (13,), dtype=np.int32), 6)
    for _ in range(3):
        eng.step()
    assert len(first.tokens) >= 1 and not first.done
    second = eng.submit(rng.integers(0, cfg.vocab, (5,), dtype=np.int32), 3)
    done = eng.run()
    assert {r.rid for r in done} == {first.rid, second.rid}
    return rec, eng, (first, second)


@pytest.fixture(params=[None, 8], ids=["unbudgeted", "budget8"])
def served(request, monkeypatch):
    return serve(monkeypatch, request.param)


def test_phases_tile_inside_one_step(served):
    rec, eng, _ = served
    steps = rec.named("engine.step")
    phases = sorted((s for s in rec.spans if s[0] != "engine.step"),
                    key=lambda s: s[1])
    dispatch = [d[1] for d in rec.named("engine.decode.dispatch")]
    for s in steps:  # numbered by the decode steps before it
        assert s[3]["step_num"] == sum(t < s[1] for t in dispatch)
    for name, a, b, _ in phases:
        owners = [s for s in steps if s[1] <= a and b <= s[2]]
        assert len(owners) == 1, name
    for x, y in zip(phases, phases[1:]):
        assert x[2] <= y[1], (x[0], y[0])  # siblings do not overlap
    assert len(rec.named("engine.retire")) == 2 * len(steps)


def test_one_decode_wait_per_decode_step(served):
    rec, eng, _ = served
    waits = rec.named("engine.decode.wait")
    dispatches = rec.named("engine.decode.dispatch")
    assert len(waits) == len(dispatches) == eng.steps > 0
    for step in rec.named("engine.step"):
        inside = [w for w in waits if step[1] <= w[1] < step[2]]
        assert len(inside) <= 1
    assert all(1 <= d[3]["slots"] <= 2 for d in dispatches)
    assert max(d[3]["slots"] for d in dispatches) == 2  # both decoded


def test_prefill_spans_name_their_requests(served):
    rec, eng, reqs = served
    rids = sorted(r.rid for r in reqs)
    waits = [s for s in rec.named("engine.prefill.wait")
             if "probe" not in s[3]]
    assert sorted(w[3]["rid"] for w in waits) == rids  # one per prefill
    assert sorted(s[3]["rid"] for s in rec.named("engine.page_scatter")) \
        == rids
    admits = [s for s in rec.named("engine.admit") if "rid" in s[3]]
    assert sorted(a[3]["rid"] for a in admits) == rids
    for a in admits:
        assert 0 <= a[3]["slot"] < 2 and a[3]["pages"] >= 1
    for r in reqs:
        chunks = [c[3]["n_tokens"] for c in rec.named("engine.prefill_chunk")
                  if c[3]["rid"] == r.rid]
        assert sum(chunks) == len(r.prompt)
    assert len([c for c in rec.named("engine.prefill_chunk")
                if c[3]["rid"] == reqs[0].rid]) == 2  # 13 tokens, chunk 8


def test_budget_probe_is_a_prefill_wait(monkeypatch):
    rec, _, _ = serve(monkeypatch, 8)
    probes = [s for s in rec.named("engine.prefill.wait") if "probe" in s[3]]
    assert probes  # the first chunk cost is always sampled


def test_first_token_stamped_after_it_reaches_the_host(served):
    rec, _, reqs = served
    for r in reqs:
        wait = [w for w in rec.named("engine.prefill.wait")
                if w[3].get("rid") == r.rid and "probe" not in w[3]]
        assert r.t_first >= wait[0][2]
        assert r.token_times[0] == r.t_first


def test_real_spans_run_with_no_profiler():
    """The profiler's own span classes, with no profiler running."""
    cfg, params = _cfg_params()
    eng = ServingEngine(params, cfg, max_slots=2, max_len=64, page_size=8,
                        prefill_chunk=8)
    r = eng.submit(np.arange(11, dtype=np.int32), 4)
    eng.run()
    assert len(r.tokens) == 4
