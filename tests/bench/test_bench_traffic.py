"""The traffic generator: deterministic per seed, lengths in range, the
same work for every seed."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

HERE = Path(__file__).resolve().parent
MIXES = {"batch": HERE.parents[1] / "bench" / "traffic" / "batch.json",
         "train": HERE.parents[1] / "bench" / "traffic" / "train.json",
         "open-loop": HERE / "fixtures" / "tiny-chat.json"}
SEED = 2**33 + 12345  # more than 32 bits


def load(name):
    return json.loads(MIXES[name].read_text())


@pytest.mark.parametrize("mix", ["batch", "open-loop"])
def test_same_seed_same_requests(mix):
    m = load(mix)
    a = traffic.serve_requests(m, SEED, 151936)
    b = traffic.serve_requests(m, SEED, 151936)
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.output_lens, b.output_lens)
    if a.due_s is not None:
        assert np.array_equal(a.due_s, b.due_s)
    for i in (0, 7):
        assert np.array_equal(a.prompt(i), b.prompt(i))


@pytest.mark.parametrize("mix", ["batch", "open-loop"])
def test_lengths_in_range_and_same_work_for_every_seed(mix):
    m = load(mix)
    a = traffic.serve_requests(m, SEED, 151936)
    b = traffic.serve_requests(m, 3, 151936)
    p, o = m["prompt_tokens"], m["output_tokens"]
    for r in (a, b):
        assert r.prompt_lens.min() >= p["min"]
        assert r.prompt_lens.max() <= p["max"]
        assert r.output_lens.min() >= o["min"]
        assert r.output_lens.max() <= o["max"]
        assert len(r.prompt(0)) == r.prompt_lens[0]
        assert r.prompt(0).max() < 151936
    # a window reaches only the head of the template, so the lengths
    # keep its order: every seed offers the same work in every window
    assert np.array_equal(a.prompt_lens, b.prompt_lens)
    assert np.array_equal(a.output_lens, b.output_lens)
    if a.due_s is not None:
        assert np.array_equal(a.due_s, b.due_s)
        assert np.all(np.diff(a.due_s) >= 0)
    assert not np.array_equal(a.prompt(0)[:8], b.prompt(0)[:8])


def test_gamma_arrivals_have_the_stated_rate_and_burstiness():
    rng = traffic.rng_for(1, 0)
    g = traffic.draw_gaps({"process": "gamma", "rate_per_s": 5.0, "cv": 2.0},
                          200_000, rng)
    assert g.mean() == pytest.approx(0.2, rel=0.03)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.05)


def test_buckets_cover_the_length_range():
    assert traffic.buckets(128, 2048, 512) == [512, 1024, 1536, 2048]
    assert traffic.buckets(64, 3072, 512) == [512, 1024, 1536, 2048, 2560, 3072]
    assert traffic.buckets(600, 1000, 512) == [1024]


def test_packed_docs_deterministic_and_distinct_rows():
    job = load("train")
    d = traffic.PackedDocs(job, SEED, 151936, 151645)
    a, b = d.batch(0), d.batch(0)
    assert a.shape == (job["batch"], job["seq_len"] + 1)
    assert np.array_equal(a, b)
    assert not np.array_equal(d.batch(0), d.batch(1))
    assert len({row.tobytes() for row in a}) == len(a)
    assert a.max() < 151936 and a.min() >= 0
    assert (a == 151645).any()  # documents are separated
