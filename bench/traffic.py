"""The one generator every traffic mix goes through.

A mix file fixes a *template*: the lengths of every request and, for an
open loop, every arrival time, drawn once from the mix's own
``template_seed``.  A run's ``--seed`` draws the token ids (and the
weights); the lengths keep the template's order, so that every seed
offers the same work and the same bursts inside a window that reaches
only the first part of the template.

Length distributions: ``{"dist": "lognormal", "median": m, "sigma": s,
"min": a, "max": b}`` (clipped to [a, b]) or ``{"dist": "uniform",
"min": a, "max": b}``.  Arrivals: ``{"process": "backlog"}`` (a closed
queue kept full), ``"poisson"`` with ``rate_per_s``, or ``"gamma"``
with ``rate_per_s`` and ``cv`` (coefficient of variation of the gaps;
cv 1 is Poisson, cv 2 the bursts BurstGPT reports).
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: arrivals the template covers for an open loop: more than any window
#: (``run_seconds`` <= 51) plus its drain
TEMPLATE_SECONDS = 180.0


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """NumPy generator for ``seed`` (any size) and a sub-stream id."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def draw_gaps(arrivals: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    rate = float(arrivals["rate_per_s"])
    if arrivals["process"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    if arrivals["process"] == "gamma":
        cv = float(arrivals["cv"])
        shape = 1.0 / cv**2
        return rng.gamma(shape, 1.0 / (rate * shape), n)
    raise ValueError(f"unknown arrival process {arrivals['process']!r}")


@dataclasses.dataclass
class Requests:
    """The requests of one run, in submission order."""
    due_s: np.ndarray | None  # offsets from the window start; None = backlog
    prompt_lens: np.ndarray
    output_lens: np.ndarray
    seed: int
    vocab: int

    def __len__(self) -> int:
        return len(self.prompt_lens)

    def prompt(self, i: int, size: int | None = None) -> np.ndarray:
        """Token ids of the ``i``-th submission, drawn from the seed, at
        the prompt length of request ``size`` (default ``i``; a backlog
        cycles through the lengths while every prompt differs)."""
        rng = rng_for(self.seed, 1, i)
        n = self.prompt_lens[i if size is None else size]
        return rng.integers(0, self.vocab, int(n), dtype=np.int32)


def serve_requests(mix: dict, seed: int, vocab: int) -> Requests:
    tmpl = rng_for(mix["template_seed"], 0)
    arrivals = mix["arrivals"]
    if arrivals["process"] == "backlog":
        n = int(mix["template_requests"])
        due = None
    else:
        mean_gap = 1.0 / float(arrivals["rate_per_s"])
        n = int(np.ceil(TEMPLATE_SECONDS / mean_gap * 1.5)) + 16
        due = np.cumsum(draw_gaps(arrivals, n, tmpl))
        keep = due < TEMPLATE_SECONDS
        n = int(keep.sum())
        due = due[:n]
    prompts = draw_lengths(mix["prompt_tokens"], n, tmpl)
    outputs = draw_lengths(mix["output_tokens"], n, tmpl)
    return Requests(due, prompts, outputs, seed, vocab)


def buckets(lo: int, hi: int, chunk: int) -> list[int]:
    """Dense-cache capacities the engine's prefill compiles for prompts
    of ``lo``..``hi`` tokens (``ceil(n / chunk) * chunk``)."""
    first = max(1, -(-lo // chunk))
    last = -(-hi // chunk)
    return [k * chunk for k in range(first, last + 1)]


class PackedDocs:
    """Training batches: documents of heavy-tailed length, Zipf-shaped
    token ids, packed back to back with a separator into rows of
    ``seq_len + 1`` tokens.  ``batch(step)`` is a pure function of
    (seed, step), and every row of every step differs."""

    def __init__(self, job: dict, seed: int, vocab: int, separator: int):
        self.job, self.seed, self.vocab = job, seed, vocab
        self.separator = separator
        self.rows, self.seq = int(job["batch"]), int(job["seq_len"])

    def batch(self, step: int) -> np.ndarray:
        rng = rng_for(self.seed, 2, step)
        need = self.rows * (self.seq + 1)
        out = np.empty(need, np.int32)
        filled = 0
        while filled < need:
            n = int(draw_lengths(self.job["doc_tokens"], 1, rng)[0])
            doc = np.minimum(rng.zipf(self.job["zipf_a"], n) - 1,
                             self.vocab - 1)
            piece = np.append(doc, self.separator)[: need - filled]
            out[filled: filled + len(piece)] = piece
            filled += len(piece)
        return out.reshape(self.rows, self.seq + 1)
