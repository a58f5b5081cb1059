"""Median wall time (ms) of the engine's decode calls in the window: the
program's ``engine.decode`` spans (host clock, synced on the sampled
tokens inside the span)."""
import statistics

from harness import spans


def read(r):
    d = spans.durations_ms(r.spans or [], "engine.decode")
    return statistics.median(d) if d else None
