"""90th percentile, over the requests due in the window, of the wait from
when each was due to when the engine admitted it (``Request.t_admit``,
stamped before any model work, on the harness's own clock)."""

from bench import window as win


def read(run):
    waits = [t.req.t_admit - t.due for t in run.judged
             if t.req.t_admit is not None]
    return 1e3 * win.percentile(waits, 90) if waits else None
