"""Median host time (ms) of one engine step: each ``engine.step`` span of
the window less the ``engine.readback`` spans inside it, in which the
host waits on the chip for the sampled tokens. What is left is the
host's own work of a step (plan, upload, dispatch, finish, bookkeeping),
during which nothing it dispatched is queued on the chip."""
import statistics


def read(r):
    spans = r.spans or []
    waits = [(t, t + d) for name, t, d, _ in spans if name == "engine.readback"]
    host = [d - sum(b - a for a, b in waits if t <= a and b <= t + d)
            for name, t, d, _ in spans if name == "engine.step"]
    return statistics.median(host) / 1e3 if host else None
