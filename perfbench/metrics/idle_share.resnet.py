"""Share (%) of the traced window in which no operation ran on the chip
(1 - union of device-busy intervals / window, harness.devtrace)."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.idle_share
