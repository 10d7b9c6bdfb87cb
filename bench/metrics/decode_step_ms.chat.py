"""Device milliseconds per call of the jitted paged decode program
(``serve_step``), from the program events of the trace."""

PROGRAM = "serve_step"


def read(run):
    d = run.trace.matching("modules", PROGRAM)
    return 1e3 * sum(d) / len(d) if d else None
