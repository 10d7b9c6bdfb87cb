"""A serving cell: the program's ``ServingEngine`` driven by one traffic
mix, timed from the host, its served tokens checked against the plain
reference once the window has closed.

Every time is taken on ``time.monotonic``, the engine's own clock.  A
request is timed from when it was *due*, and its tokens from when the
harness sees them on the host, after ``step()`` returns.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from math import inf

import numpy as np

from bench import traffic, weights
from bench.peaks import peak_bytes
from bench import window as win

clock = time.monotonic
#: arithmetic of the control, the precision below the bfloat16 served
CONTROL = "fp8"


@dataclasses.dataclass
class StepRec:
    """One engine step as the harness sees it: when it ran, the prompts
    it prefilled and the cache length of each sequence it decoded."""
    t0: float
    t1: float
    prefills: list
    decode_lens: list


@dataclasses.dataclass
class Tracked:
    idx: int          # index into the run's Requests
    req: object       # the engine's Request
    due: float        # host time it was due
    submitted: float
    want: int         # output tokens asked for
    stamps: list      # host time each token was seen
    ended: float = inf  # host time its end (done or cancelled) was seen

    @property
    def finished(self) -> bool:
        return self.req.done or self.req.cancelled

    @property
    def served(self) -> bool:
        """Done with every token asked for (no end-of-sequence id is
        set, so nothing ends a request early)."""
        return self.req.done and len(self.req.tokens) == self.want


def failed(t: Tracked, eng) -> bool:
    """Cancelled or shed, ended short of its length, or gone from the
    engine while unfinished."""
    from bench import program

    if t.finished:
        return not t.served
    return not program.holds(eng, t.req)


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class LoadLoop:
    """Submits requests and steps the engine, stamping every token."""

    def __init__(self, eng, reqs: traffic.Requests, depth: int = 0):
        self.eng, self.reqs, self.depth = eng, reqs, depth
        self.live: list[Tracked] = []
        self.all: list[Tracked] = []
        self.steps: list[StepRec] = []
        self.record = False
        self.next_k = 0  # next request of the template to submit

    def submit(self, idx: int, size: int, due: float) -> None:
        want = int(self.reqs.output_lens[size])
        with _span("bench.generate"):
            prompt = self.reqs.prompt(idx, size)
        with _span("bench.submit"):
            r = self.eng.submit(prompt, want)
        t = Tracked(idx, r, due, clock(), want, [])
        self.live.append(t)
        self.all.append(t)

    def pump_backlog(self, until: float, steps: int | None = None) -> None:
        """Keep ``depth`` requests queued and step the engine, until the
        clock reaches ``until`` or ``steps`` steps have run."""
        n, k = len(self.reqs), 0
        while clock() < until and (steps is None or k < steps):
            while self.eng.pending < self.depth:
                self.submit(self.next_k, self.next_k % n, clock())
                self.next_k += 1
            self.step()
            k += 1

    def pump_open(self, due, until: float, stop=None) -> None:
        """Submit each request once it falls ``due`` (host times) and
        step the engine, until the clock reaches ``until`` or ``stop()``
        holds."""
        eng, n = self.eng, len(due)
        while True:
            now = clock()
            if now >= until or (stop is not None and stop()):
                return
            while self.next_k < n and due[self.next_k] <= now:
                self.submit(self.next_k, self.next_k,
                            float(due[self.next_k]))
                self.next_k += 1
            if eng.pending or eng.active:
                self.step()
            else:
                nxt = due[self.next_k] if self.next_k < n else until
                time.sleep(max(0.0, min(nxt, until) - clock()))

    def step(self) -> None:
        before = [(t, len(t.req.tokens)) for t in self.live]
        t0 = clock()
        with _span("bench.step"):
            self.eng.step()
        t1 = clock()
        prefills, lens = [], []
        keep = []
        for t, n0 in before:
            n1 = len(t.req.tokens)
            if n1 > n0:
                t.stamps += [t1] * (n1 - n0)
                plen = len(t.req.prompt)
                if n0 == 0:
                    prefills.append(plen)
                    if n1 > 1:
                        lens.append(plen + 1)
                else:
                    lens.append(plen + n0)
            if t.finished:
                t.ended = t1
            else:
                keep.append(t)
        self.live = keep
        if self.record:
            self.steps.append(StepRec(t0, t1, prefills, lens))


def warm_up(eng, mix: dict, vocab: int) -> None:
    """Compile every program the mix's lengths reach: one prefill (and
    page scatter) per dense-cache bucket, and the decode step."""
    chunk = int(mix["engine"]["prefill_chunk"])
    p = mix["prompt_tokens"]
    for n in traffic.buckets(int(p["min"]), int(p["max"]), chunk):
        eng.submit(np.arange(n, dtype=np.int32) % vocab, 2)
    eng.run()
    eng.take_done()


def serve_window(drv: LoadLoop, mix: dict, seconds: float, trace,
                 due=None) -> dict:
    """The measured window and, for an open loop, the drain after it,
    arrivals going on.  Returns the window's bounds, the requests it is
    judged on and how many of them failed.

    Backlog: judged are the requests whose end was seen in the window,
    and any the engine dropped unfinished.  Open loop (``due``: host
    times of the arrivals, in order): judged are the requests due in the
    window, waited for until ``drain_cap_s`` after its close; one still
    decoding then is late, not failed, and is not checked."""
    eng = drv.eng
    backlog = due is None
    drv.record = True
    if trace is not None:
        trace.start()
    t0 = clock()
    with _span("bench.window"):
        if backlog:
            drv.pump_backlog(t0 + seconds)
            t1 = drv.steps[-1].t1
        else:
            drv.pump_open(due, t0 + seconds)
            # the window closes when its last step has returned
            t1 = max([t0 + seconds] + [s.t1 for s in drv.steps[-1:]])
    if trace is not None:
        trace.stop()
    drv.record = False
    if backlog:
        judged = [t for t in drv.all if t0 <= t.ended <= t1
                  or (not t.finished and failed(t, eng))]
    else:
        # arrivals due before the close, some submitted only after it
        n_due = int(np.searchsorted(due, t1))

        def settled():
            return drv.next_k >= n_due and all(
                t.finished for t in drv.all[:n_due] if t.due >= t0)

        drv.pump_open(due, t1 + float(mix["drain_cap_s"]), stop=settled)
        judged = [drv.all[i] for i in win.due_in(
            [t.due for t in drv.all], t0, t1)]
    bad = [t for t in judged if failed(t, eng)
           or (not backlog and not t.stamps)]
    return {"t0": t0, "t1": t1, "judged": judged, "failed": len(bad),
            "t_end": clock()}


def end_to_end(mix: dict, drv: LoadLoop, w: dict) -> dict:
    t0, t1 = w["t0"], w["t1"]
    stamps = [s for t in drv.all for s in t.stamps]
    out = {"output_tokens_per_s": win.rate(win.tokens_in(stamps, t0, t1),
                                           t0, t1)}
    if mix["arrivals"]["process"] != "backlog":
        # every request due in the window; one with no token yet has
        # failed, and counts at the time the drain gave up on it
        ttft = [win.ttft(t.due, t.stamps or [w["t_end"]])
                for t in w["judged"]]
        itl = [g for t in w["judged"] for g in win.gaps(t.stamps)]
        out["ttft_p90_ms"] = 1e3 * win.percentile(ttft, 90) if ttft else inf
        out["itl_p99_ms"] = 1e3 * win.percentile(itl, 99) if itl else inf
    return out


def pick_sample(judged, check: dict, seed: int) -> list:
    """The finished requests the reference checks: the longest, then
    others drawn from the seed until enough served tokens are in."""
    done = [t for t in judged if t.served]
    if not done:
        return []
    done.sort(key=lambda t: (len(t.req.prompt) + len(t.req.tokens), t.idx))
    pick = [done[-1]]
    rest = done[:-1]
    order = traffic.rng_for(seed, 3).permutation(len(rest))
    served = len(done[-1].req.tokens)
    for j in order:
        if (len(pick) >= int(check["requests"])
                or served >= int(check["served_tokens"])):
            break
        pick.append(rest[j])
        served += len(rest[j].req.tokens)
    return pick


def reference_gaps(model, config: dict, ref, seed: int, dtype, sample,
                   max_len: int, max_out: int, control: bool = False):
    """For each sampled request, how far each served token's logit lies
    below the reference's best at its position (and, with ``control``,
    the same for the token the fp8 control puts first there)."""
    import jax
    import jax.numpy as jnp

    w = weights.make(model, config, seed, dtype)
    fn = _gap_fn(ref, config, max_len, max_out, control)
    out = []
    for t in sample:
        prompt = np.asarray(t.req.prompt, np.int32)
        toks = np.asarray(t.req.tokens, np.int32)
        seq = np.zeros(max_len, np.int32)
        full = np.concatenate([prompt, toks[:-1]])
        seq[:len(full)] = full
        served = np.zeros(max_out, np.int32)
        served[:len(toks)] = toks
        res = fn(w, jnp.asarray(seq), jnp.int32(len(prompt) - 1),
                 jnp.asarray(served))
        res = jax.tree.map(lambda a: np.asarray(a)[:len(toks)], res)
        out.append(res)
    return out


_GAP_FNS: dict = {}


def _gap_fn(ref, config, max_len, max_out, control):
    import jax
    import jax.numpy as jnp

    key = (config["name"], max_len, max_out, control)
    if key in _GAP_FNS:
        return _GAP_FNS[key]
    block = 256 if max_out % 256 == 0 else max_out

    def rows(h, pos0):
        idx = jnp.clip(pos0 + jnp.arange(max_out), 0, max_len - 1)
        return h[idx].reshape(max_out // block, block, -1)

    def fn(w, seq, pos0, served):
        h = rows(ref.hidden(w, config, seq, "f32"), pos0)
        sv = served.reshape(max_out // block, block)
        hc = rows(ref.hidden(w, config, seq, CONTROL), pos0) if control else h

        def per_block(a):
            hb, cb, sb = a
            lg = ref.head(w, hb, "f32")
            best = jnp.max(lg, -1)
            gap = best - jnp.take_along_axis(lg, sb[:, None], -1)[:, 0]
            if not control:
                return gap, gap
            top = jnp.argmax(ref.head(w, cb, CONTROL), -1)
            cgap = best - jnp.take_along_axis(lg, top[:, None], -1)[:, 0]
            return gap, cgap

        gap, cgap = jax.lax.map(per_block, (h, hc, sv))
        if control:
            return {"gap": gap.reshape(-1), "control_gap": cgap.reshape(-1)}
        return {"gap": gap.reshape(-1)}

    _GAP_FNS[key] = jax.jit(fn)
    return _GAP_FNS[key]


def run_cell(cell, seed: int, seconds: float, tracer, started: float,
             compiles, check: str = "program") -> dict:
    """One run of a serving cell; returns the pieces of its result.
    ``check`` is "program" (the benchmark's comparison), "control" (the
    same, plus the fp8 control's readings on the same tokens) or
    "none" (the knee sweep)."""
    import jax
    import jax.numpy as jnp

    from bench import program
    from bench.spec import reference_module

    mix, config, model = cell.traffic, cell.config, cell.model
    eng_s = mix["engine"]
    cfg = program.model_config(model, config)
    dtype = jnp.dtype(eng_s["weights_dtype"])
    w = weights.make(model, config, seed, dtype)
    program.check_layout(model, w, cfg)
    params = program.to_program(model, w)
    del w
    max_len = int(mix["prompt_tokens"]["max"]) + int(mix["output_tokens"]["max"])
    eng = program.serving_engine(params, cfg, eng_s, max_len,
                                 int(cell.settings["kv_pool_bytes"]))
    vocab = int(config["vocab_size"])
    warm_up(eng, mix, vocab)
    reqs = traffic.serve_requests(mix, seed, vocab)
    arrivals = mix["arrivals"]
    drv = LoadLoop(eng, reqs, int(arrivals.get("depth_per_slot", 0))
                   * eng.max_slots)
    due = None
    # pre-roll: the load runs before the window, so that the window
    # sees slots in every stage of their requests' lives
    if arrivals["process"] == "backlog":
        drv.pump_backlog(inf, steps=int(mix.get("preroll_steps", 0)))
    else:
        start = clock() + float(mix.get("preroll_s", 0.0))
        due = start - float(mix.get("preroll_s", 0.0)) + reqs.due_s
        drv.pump_open(due, start)
    jax.block_until_ready(eng.blocks)
    setup_s = clock() - started
    c0 = compiles.count
    w_ = serve_window(drv, mix, seconds, tracer, due)
    in_window = compiles.count - c0
    e2e = end_to_end(mix, drv, w_)
    backlog = eng.pending
    stats = eng.stats()
    peak = peak_bytes()
    judged = w_["judged"]
    sample = pick_sample(judged, mix["check"], seed)
    drv.eng = eng = params = None
    gc.collect()
    t_ref = clock()
    gaps = [] if check == "none" else reference_gaps(
        model, config, reference_module(config), seed, dtype, sample, max_len,
        int(mix["output_tokens"]["max"]), control=check == "control")
    gap = max(float(np.max(g["gap"])) for g in gaps) if gaps else float("inf")
    control = ({"logit_gap": max(float(np.max(g["control_gap"]))
                                 for g in gaps)}
               if check == "control" and gaps else None)
    return {
        "control": control, "backlog_at_close": backlog,
        "setup_s": setup_s, "e2e": e2e, "compiles_in_window": in_window,
        "attempted": len(judged), "failed": w_["failed"], "peak": peak,
        "checks": {"logit_gap": gap},
        "notes": {
            "engine": stats,
            "checked_requests": len(sample),
            "checked_tokens": int(sum(len(t.req.tokens) for t in sample)),
            "reference_s": clock() - t_ref,
            "generator_late_p99_ms": 1e3 * win.percentile(
                [t.submitted - t.due for t in judged], 99) if judged else 0.0,
            "window": [w_["t0"], w_["t1"]],
        },
        "steps": drv.steps, "judged": judged, "window": (w_["t0"], w_["t1"]),
        "max_slots": int(eng_s["max_slots"]),
    }
