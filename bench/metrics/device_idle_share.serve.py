"""Share of the traced window with no operation running on the device:
1 - (union of device op intervals) / window."""


def read(run):
    return 100.0 * run.trace.idle_share()
