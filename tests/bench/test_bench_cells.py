"""Whole runs of fixture cells on the CPU, the chip check skipped: a
cell added as files runs with no other file edited, `correct` holds for
the sound program, and comes out false for the control and for each
fault the cells can have, planted underneath the timed path."""

from __future__ import annotations

import time

import numpy as np
import pytest

from bench import run as bench_run
from bench import spec

SEED = 2**33 + 77


def run(root, workload, seconds=2.0):
    return bench_run.run(workload, SEED, seconds, False, root=root,
                         require_chip=False)


@pytest.mark.parametrize("workload,metrics", [
    ("tiny.chat", {"ttft_p90_ms", "itl_p99_ms", "setup_s"}),
    ("tiny.batch", {"output_tokens_per_s", "setup_s"}),
    ("tiny.train", {"train_tokens_per_s", "setup_s"}),
])
def test_fixture_cell_runs_correct(fixture_root, workload, metrics):
    line = run(fixture_root, workload)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert line["device"]["count"] == 1


def test_fixture_cell_lists_per_layer_metrics(fixture_root):
    cell = spec.load_cell("tiny.batch", fixture_root)
    names = {m["name"] for m in cell.per_layer}
    assert {"decode_occupancy", "paged_decode_roofline", "serve_mfu",
            "device_idle_share.serve"} <= names
    chat = spec.load_cell("tiny.chat", fixture_root)
    assert {m["name"] for m in chat.per_layer} == {
        "queue_wait_p90_ms", "decode_step_ms.chat", "device_idle_share.chat",
        "admission_stall_p99_ms"}
    train = spec.load_cell("tiny.train", fixture_root)
    assert {m["name"] for m in train.per_layer} == {
        "train_mfu", "device_idle_share.train"}


def _wrap_engine_jits(monkeypatch, alter_decode=None, alter_prefill=None):
    from repro.serve import engine

    real = engine._family_jits
    engine._JIT_CACHE.clear()

    def wrapped(cfg, chunk):
        pre, dec, ver = real(cfg, chunk)

        def pre2(*a, **k):
            tok, c = pre(*a, **k)
            return (alter_prefill(tok, cfg) if alter_prefill else tok), c

        def dec2(*a, **k):
            tok, c = dec(*a, **k)
            return (alter_decode(tok, cfg) if alter_decode else tok), c

        return pre2, dec2, ver

    monkeypatch.setattr(engine, "_family_jits", wrapped)


def _shift(tok, cfg):
    return (tok + 1) % cfg.vocab


def test_altered_decode_token_is_not_correct(fixture_root, monkeypatch):
    _wrap_engine_jits(monkeypatch, alter_decode=_shift)
    line = run(fixture_root, "tiny.chat")
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > \
        line["checks"]["logit_gap"]["limit"]


def test_altered_prefill_token_is_not_correct(fixture_root, monkeypatch):
    _wrap_engine_jits(monkeypatch, alter_prefill=_shift)
    assert run(fixture_root, "tiny.batch")["correct"] is False


def _wrap_engine(monkeypatch, fault):
    """Truncate every request (asks the engine for one token fewer than
    the traffic wants) or cancel a decoding request every fifth step."""
    from repro.serve.engine import ServingEngine

    real_submit, real_step = ServingEngine.submit, ServingEngine.step

    def submit(self, prompt, max_new, **kw):
        if fault == "truncated":
            max_new = max(1, max_new - 1)
        return real_submit(self, prompt, max_new, **kw)

    def step(self, *a, **kw):
        out = real_step(self, *a, **kw)
        live = [sl.req for sl in self.slots if sl.req is not None
                and len(sl.req.tokens) > 1]
        if fault == "cancelled" and live and self.steps % 5 == 0:
            self.cancel(live[0])
        return out

    monkeypatch.setattr(ServingEngine, "submit", submit)
    monkeypatch.setattr(ServingEngine, "step", step)


@pytest.mark.parametrize("workload", ["tiny.batch", "tiny.chat"])
@pytest.mark.parametrize("fault", ["truncated", "cancelled"])
def test_dropped_or_short_request_is_failed(fixture_root, monkeypatch,
                                            workload, fault):
    _wrap_engine(monkeypatch, fault)
    line = run(fixture_root, workload)
    assert line["failed"] > 0, line
    assert line["attempted"] >= line["failed"]
    assert line["correct"] is False


def test_serving_control_reads_above_the_limit(fixture_root):
    from bench import serve

    cell = spec.load_cell("tiny.chat", fixture_root)
    res = serve.run_cell(cell, SEED, 2.0, None, time.monotonic(),
                         bench_run.CompileCounter(), check="control")
    limit = cell.settings["limits"]["logit_gap"]
    assert res["checks"]["logit_gap"] <= limit
    assert res["control"]["logit_gap"] > limit


def _wrap_train_step(monkeypatch, fault):
    import repro.train.step as tstep

    real = tstep.make_train_step

    def make(cfg, opt, **kw):
        step = real(cfg, opt, **kw)

        def faulty(state, batch):
            if fault == "unchanged":
                _, metrics = step(state, batch)
                return state, metrics
            half = batch["tokens"].shape[0] // 2
            return step(state, {"tokens": batch["tokens"][:half]})

        return faulty

    monkeypatch.setattr(tstep, "make_train_step", make)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_fault_is_not_correct(fixture_root, monkeypatch, fault):
    _wrap_train_step(monkeypatch, fault)
    line = run(fixture_root, "tiny.train", seconds=1.0)
    assert line["correct"] is False
    failing = [k for k, c in line["checks"].items()
               if c["value"] > c["limit"]]
    assert failing, line["checks"]


def test_train_control_reads_above_a_limit(fixture_root):
    from bench import train

    cell = spec.load_cell("tiny.train", fixture_root)
    res = train.run_cell(cell, SEED, 1.0, None, time.monotonic(),
                         bench_run.CompileCounter(), check="control")
    limits = cell.settings["limits"]
    assert all(v <= limits[k] for k, v in res["checks"].items())
    for name in (train.CONTROL, "half_batch"):
        got = res["control"][name]
        assert any(got[k] > limits[k] for k in got), (name, got)
    assert np.isfinite(list(res["checks"].values())).all()
