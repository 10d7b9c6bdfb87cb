"""Host milliseconds per engine step: the mean, over the
``engine.step`` spans wholly inside the window, of each step's duration
less the time its ``*.wait`` children cover (the host blocked on the
device).  What is left is retirement, admission, page allocation,
dispatch and emission: the host's share of the step.  None where the
program writes no engine spans."""

from bench import spans


def read(run):
    t = spans.host_step_s(spans.of_run(run), run.trace.t0, run.trace.t1)
    return 1e3 * sum(t) / len(t) if t else None
