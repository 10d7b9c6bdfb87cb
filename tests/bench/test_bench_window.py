"""Window arithmetic: tails over all requests, rates over the whole
window, requests due in the window and finishing after it."""

import pytest

from bench import window


def test_percentile_nearest_rank_over_all_values():
    xs = list(range(1, 101))  # 1..100
    assert window.percentile(xs, 90) == 90
    assert window.percentile(xs, 99) == 99
    assert window.percentile(xs, 100) == 100
    assert window.percentile([5.0], 90) == 5.0
    assert window.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        window.percentile([], 90)


def test_rate_over_whole_window():
    assert window.rate(300, 10.0, 13.0) == pytest.approx(100.0)
    with pytest.raises(ValueError):
        window.rate(1, 2.0, 2.0)


def test_due_in_window_and_finished_after():
    due = [0.5, 1.0, 9.99, 10.0, 12.0]
    assert window.due_in(due, 1.0, 10.0) == [1, 2]
    # a request due at 9.99 whose tokens come after the close is judged
    # on its own times: ttft runs from when it was due
    stamps = [10.4, 10.45, 10.6]
    assert window.ttft(9.99, stamps) == pytest.approx(0.41)
    assert window.gaps(stamps) == [pytest.approx(0.05), pytest.approx(0.15)]
    # but only its tokens inside the window count for the rate
    assert window.tokens_in(stamps, 1.0, 10.0) == 0
    assert window.tokens_in([1.0, 5.0, 10.0, 10.1], 1.0, 10.0) == 3


def test_queue_wait_over_requests_due_in_the_window():
    from types import SimpleNamespace as NS

    from bench import spec

    read = spec.metric_reader("queue_wait_p90_ms")
    judged = [NS(due=float(i), req=NS(t_admit=i + 0.01 * (i + 1)))
              for i in range(10)]
    # waits 10..100 ms: the 90th percentile of the ten is the ninth
    assert read(NS(judged=judged)) == pytest.approx(90.0)
    judged.append(NS(due=3.0, req=NS(t_admit=None)))  # never admitted
    assert read(NS(judged=judged)) == pytest.approx(90.0)
    assert read(NS(judged=[])) is None


class _Req:
    def __init__(self, prompt, want):
        self.prompt, self.max_new, self.tokens = prompt, want, []
        self.cancelled, self.t_admit = False, None

    @property
    def done(self):
        return len(self.tokens) >= self.max_new


class _Engine:
    """One token per request per step; a step takes ``step_s``."""

    max_slots = 8

    def __init__(self, step_s):
        self.step_s, self._queue = step_s, []
        self.slots = [type("Slot", (), {"req": None})()
                      for _ in range(self.max_slots)]

    @property
    def pending(self):
        return len(self._queue)

    @property
    def active(self):
        return sum(s.req is not None for s in self.slots)

    def submit(self, prompt, want):
        r = _Req(prompt, want)
        self._queue.append(r)
        return r

    def step(self):
        import time

        time.sleep(self.step_s)
        for s in self.slots:
            if s.req is None and self._queue:
                s.req = self._queue.pop(0)
        for s in self.slots:
            if s.req is not None:
                s.req.tokens.append(1)
                if s.req.done:
                    s.req = None


def test_open_loop_judges_every_request_due_before_the_close():
    import numpy as np

    from bench import serve, traffic

    reqs = traffic.Requests(np.array([0.02, 0.05, 0.29, 0.5]),
                            np.full(4, 4), np.full(4, 3), 1, 16)
    drv = serve.LoadLoop(_Engine(0.1), reqs)
    t = serve.clock()
    w = serve.serve_window(drv, {"drain_cap_s": 5.0}, 0.3, None,
                           due=t + reqs.due_s)
    # the request due at 0.29 s is submitted after the last step of the
    # window returns, and judged all the same; the one due at 0.5 is not
    assert sorted(j.idx for j in w["judged"]) == [0, 1, 2]
    assert w["failed"] == 0 and all(j.served for j in w["judged"])
