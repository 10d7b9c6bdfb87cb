"""99th percentile, over the window's ``engine.step`` spans that
dispatch a decode, of the time from the step's start to that dispatch:
what the decoding slots waited on retirement, admission and prefill
before their token.  None where the program writes no engine spans."""

from bench import spans
from bench import window as win


def read(run):
    t = spans.admission_stalls_s(spans.of_run(run), run.trace.t0,
                                 run.trace.t1)
    return 1e3 * win.percentile(t, 99) if t else None
