"""Median wall time (ms) of the engine's prefill calls in the window: the
program's ``engine.prefill`` spans (host clock, synced on the sampled
tokens inside the span)."""
import statistics

from harness import spans


def read(r):
    d = spans.durations_ms(r.spans or [], "engine.prefill")
    return statistics.median(d) if d else None
